"""Compile-only checks for a described TPU v5e chip (nothing runs).

The TPU compiler is installed beside JAX, so these compile the main path's
Pallas kernels and the whole one-node train step for a v5e that is
described, not attached: what the chip's compiler refuses (a block not
aligned to the tiling, too much VMEM, a program over the chip's 16 GB)
fails here at no chip time.  Each compiled program must carry the kernel
as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.  The persistent compilation cache is off around these
compiles (a TPU executable written to it cannot be read back without the
chip).
"""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_config
from repro.core.optimizers import make_optimizer
from repro.core.planes import LANES, PlaneLayout
from repro.core.update_spec import MathCtx
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fused_update import make_plane_stage
from repro.launch import train
from repro.train.step import build_train_step
from repro.train.train_state import abstract_train_state, model_plane_layout

ROOT = os.path.join(os.path.dirname(__file__), "..")
HBM_BYTES = 16 * 2**30  # one v5e chip
QWEN3 = get_config("qwen3-0.6b")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize(
    "kind,op,names",
    [("pre", "grad_step", ("x", "g")), ("post", "decentlam_post", ("x", "mix", "m"))],
)
def test_fused_plane_stage_compiles_at_qwen3_rows(one_chip, kind, op, names):
    """DecentLaM's two plane stages over qwen3-0.6b's whole f32 bucket."""
    layout = model_plane_layout(QWEN3)
    planes = {
        key: jax.ShapeDtypeStruct((rows, LANES), jnp.float32, sharding=one_chip)
        for key, rows in layout.rows.items()
    }
    stage = make_plane_stage("pallas")
    ctx = MathCtx(beta=0.9)

    def run(operands):
        return stage(kind, op, ctx, operands, {"lr": jnp.float32(1e-3)},
                     operands["x"])

    compiled = jax.jit(run).lower({n: planes for n in names}).compile()
    assert _custom_calls(compiled) == len(layout.rows)


def test_flash_attention_compiles_at_qwen3_heads(one_chip):
    """16 query heads over 8 KV heads of width 128, causal, bf16."""
    q = jax.ShapeDtypeStruct((2, 2048, QWEN3.n_heads, QWEN3.hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 2048, QWEN3.n_kv_heads, QWEN3.hd),
                              jnp.bfloat16, sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv, causal=True).compile()
    assert _custom_calls(compiled) >= 1


def test_one_node_train_step_fits_one_chip(topo, monkeypatch):
    """The chip smoke's step: qwen3-0.6b at published widths, DecentLaM on
    flat planes with the Pallas update, one node on one chip, at the
    smoke's sequence length and batch.  The chip packs planes with
    ``concat`` (the CPU takes ``gather``), so the compile does too."""
    smoke = _chip_smoke()
    args = train.parse_args(smoke.step_argv("pallas"))
    cfg, tcfg = train.model_config(args), train.train_config(args)
    monkeypatch.setattr(
        PlaneLayout, "pack", functools.partialmethod(PlaneLayout.pack, impl="concat")
    )
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    step, sspecs, bspecs, channel = build_train_step(cfg, tcfg, mesh)
    state = abstract_train_state(
        cfg, make_optimizer(tcfg.opt_config()), 1, 1, channel=channel,
        plane_layout=model_plane_layout(cfg),
    )
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        state, sspecs,
    )
    batch = {
        k: jax.ShapeDtypeStruct((args.per_node_batch, args.seq_len), jnp.int32,
                                sharding=NamedSharding(mesh, bspecs[k]))
        for k in ("tokens", "targets")
    }
    compiled = step.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 16 GiB"
    assert _custom_calls(compiled) > 0
