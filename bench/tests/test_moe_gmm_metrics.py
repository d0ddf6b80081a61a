"""The expert layer's readers, ``moe_gmm_ms`` and ``moe_gmm_roofline``, on
a trace made by hand: two chips, each running the three ``moe_gmm`` kernels
beside other ops, and on a trace with no such kernel (a program without the
grouped expert layer), where both read nothing."""

import json
from pathlib import Path

import pytest

import spec
import trace_reduce as tr

CONFIG = Path(spec.BENCH) / "configs" / "granite-3.0-1b-a400m.json"
TRAFFIC = Path(spec.BENCH) / "traffic" / "1node.s2048.b4.json"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(names):
    """Per chip, in a window of [0, 1] s: each named op runs 10 ms, one
    after another, and a fusion runs 5 ms beside each."""
    ops = {}
    for dev in (0, 1):
        lst = []
        for i, name in enumerate(names):
            t = 0.1 + 0.02 * i
            lst.append(tr.Op(t, t + 0.010, name, "custom-call"))
            lst.append(tr.Op(t + 0.011, t + 0.016, f"fusion.{i}", "fusion"))
        ops[dev] = lst
    return tr.Trace(ops=ops, async_ops={}, host=[(0.0, 1.0, "bench.dispatch")])


def _rec(names, steps=2):
    with open(CONFIG) as f:
        cfg = json.load(f)
    with open(TRAFFIC) as f:
        traffic = json.load(f)
    return {"trace": _trace(names), "window_lo": 0.0, "window_hi": 1.0,
            "steps": steps, "config": cfg, "traffic": traffic, "peaks": PEAKS}


KERNELS = ["moe_gmm_fwd.1", "moe_gmm_fwd.2", "moe_gmm_dlhs", "moe_gmm_drhs.3"]


def test_moe_gmm_ms_sums_the_named_kernels_per_step():
    reader = spec.load_reader("moe_gmm_ms")
    assert reader.UNIT == "ms"
    # four 10 ms kernels a chip over two steps: 20 ms a step
    assert reader.read(_rec(KERNELS + ["fused_update_pre_grad_step.1"])) == pytest.approx(20.0)


def test_moe_gmm_roofline_is_the_layer_flops_over_peak_time():
    reader = spec.load_reader("moe_gmm_roofline")
    assert reader.UNIT == "%"
    rec = _rec(KERNELS)
    flops = reader.flops_per_step(rec["config"], rec["traffic"])
    # 24 T k d f L with remat: 8192 tokens, top-8, d 1024, f 512, 8 layers
    assert flops == 24 * 8192 * 8 * 1024 * 512 * 8
    assert reader.read(rec) == pytest.approx(100 * flops / 197e12 / 0.020)


@pytest.mark.parametrize("name", ["moe_gmm_ms", "moe_gmm_roofline"])
def test_nothing_to_read_without_the_kernel(name):
    reader = spec.load_reader(name)
    assert reader.read(_rec(["fusion.99", "fused_update_pre_grad_step.1"])) is None
    assert reader.read({"config": _rec([])["config"]}) is None  # untraced run
