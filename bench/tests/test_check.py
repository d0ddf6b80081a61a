"""The harness on the CPU at a tiny size, with the timed path sound and
then broken underneath: each fault the cells can have must turn
``correct`` false under the limits of the chip cells.

The tiny cells (``data/``) are the program's SMOKE shapes of the two
configurations, held to limits set, as the chip cells' are, from the
program's and the control's readings at that size (``data/limits/``); the
control (the reference in float8, put in the program's place) must fail
them too.
"""

import time
from pathlib import Path

import jax
import numpy as np
import pytest

import check
import harness
import reference
import spec
from repro.core import topology as topo_mod
from repro.models import transformer as T
from repro.train import step as step_mod

DATA = Path(__file__).with_name("data")
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CELLS = ("qwen3-smoke.1node", "granite-smoke.1node", "qwen3-smoke.ring4")
SEED = 2**33 + 5


def tiny(name):
    return spec.load_cell(name, benchmark=DATA / "BENCHMARK.json", files=DATA)


def run(cell, **kw):
    res, rows = harness.run_cell(cell, SEED, 1.0, False,
                                 devices=jax.devices()[: cell.chips],
                                 peaks=PEAKS, t_start=time.perf_counter(), **kw)
    return res


def unchanged(compiled):
    """A step that returns its state unchanged (its step count aside)."""

    def step(state, batch):
        copy = jax.tree.map(lambda a: a.copy(), state)
        new, met = compiled(copy, batch)
        jax.block_until_ready(new)
        out = dict(state)
        out["step"] = new["step"]
        return out, met

    return step


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run(tiny(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "step_ms_p90", "setup_s"} or (
        res["attempted"] < 10)
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_state_left_unchanged_fails(name):
    res = run(tiny(name), wrap_step=unchanged)
    assert not res["correct"]
    assert res["checks"]["change"]["value"] > 0.9


@pytest.mark.parametrize("name", CELLS)
def test_half_batch_fails(name, monkeypatch):
    real = T.forward_loss

    def half(params, batch, *a, **k):
        b = batch["tokens"].shape[0]
        cut = {key: v[: b // 2] for key, v in batch.items()}
        return real(params, cut, *a, **k)

    monkeypatch.setattr(T, "forward_loss", half)
    res = run(tiny(name))
    assert not res["correct"], res["checks"]


def test_exchange_left_out_fails(monkeypatch):
    def isolated(name, n, **k):
        return topo_mod._static("isolated", np.eye(n))

    monkeypatch.setattr(step_mod, "build_topology", isolated)
    res = run(tiny("qwen3-smoke.ring4"))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    cell = tiny(name)
    data = harness.TrafficLM(cell.traffic, cell.config["vocab_size"], SEED)
    batches = [data.batch(k) for k in range(cell.traffic["check_steps"])]
    devs = jax.devices()
    ref = reference.run(cell.config, cell.traffic, SEED, batches, devices=devs)
    ctl = reference.run(cell.config, cell.traffic, SEED, batches, devices=devs,
                        precision="fp8")
    ok, rows = check.judge(check.numbers(ctl, ref), cell.limits)
    assert not ok, rows
