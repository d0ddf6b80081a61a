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

import contextlib
import functools
import importlib.util
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import SHAPES, get_config
from repro.core.optimizers import make_optimizer
from repro.core.planes import LANES, PlaneLayout
from repro.core.update_spec import MathCtx
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fused_update import make_plane_stage
from repro.launch import train
from repro.models.attention import attention_core, score_block_share
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


@contextlib.contextmanager
def _persistent_cache_off():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def no_persistent_cache():
    with _persistent_cache_off():
        yield


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("guard", (False, True), ids=("plain", "guard"))
@pytest.mark.parametrize(
    "kind,op,names",
    [("pre", "grad_step", ("x", "g")), ("post", "decentlam_post", ("x", "mix", "m"))],
)
def test_fused_plane_stage_compiles_at_qwen3_rows(one_chip, kind, op, names, guard):
    """DecentLaM's two plane stages over qwen3-0.6b's whole f32 bucket,
    with and without the finite guard folded in."""
    layout = model_plane_layout(QWEN3)
    planes = {
        key: jax.ShapeDtypeStruct((rows, LANES), jnp.float32, sharding=one_chip)
        for key, rows in layout.rows.items()
    }
    stage = make_plane_stage("pallas")
    ctx = MathCtx(beta=0.9, guard=guard)
    scalars = {"lr": jnp.float32(1e-3)}
    if guard:
        scalars["ok"] = jnp.float32(1.0)

    def run(operands):
        return stage(kind, op, ctx, operands, scalars, operands["x"])

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


def _compile_attention(one_chip, B: int, S: int, *, train: bool, **kw):
    """The jnp attention core at qwen3-0.6b's heads, compiled for one v5e:
    forward and backward under an outer checkpoint, as the layer scan applies
    it (``train``), or the serving prefill's forward alone."""
    x = jax.ShapeDtypeStruct((B, S, QWEN3.n_heads, QWEN3.hd), jnp.bfloat16,
                             sharding=one_chip)
    if not train:
        core = functools.partial(attention_core, causal=True, remat=False, **kw)
        return jax.jit(core).lower(x, x, x).compile()

    def loss(q, k, v, ct):
        out = attention_core(q, k, v, causal=True, remat=True, **kw)
        return jnp.sum(out.astype(jnp.float32) * ct)

    step = jax.jit(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2)))
    return step.lower(x, x, x, x).compile()


def _flops(compiled) -> float:
    cost = compiled.cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["flops"]


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_causal_attention_core_skips_masked_key_blocks(one_chip):
    """The causal core scores each q block only against its key prefix:
    0.5625 of the score area at the default 256-row blocks (0.625 at 512),
    so the compiled step does at most 0.7 of the FLOPs of the same core in
    one block over the whole score area.  (A non-causal core maps its blocks
    through a loop, whose body the cost analysis counts once.)"""
    assert score_block_share(2048, 2048, 512, True) == 0.625
    assert score_block_share(2048, 2048, 256, True) == 0.5625
    causal = _flops(_compile_attention(one_chip, 2, 2048, train=True))
    full = _flops(_compile_attention(one_chip, 2, 2048, train=True, q_block=2048))
    assert causal <= 0.7 * full, (causal, full)


@pytest.mark.parametrize("shape,B,train,limit_gib", [
    ("prefill_32k", 1, False, 1.5),
    ("train_4k", 2, True, 1.0),
])
def test_attention_core_fits_at_long_shapes(one_chip, shape, B, train, limit_gib):
    """Past the benchmark's 2048 tokens the core keeps blocks of at most 512
    rows: one 32k prefill sequence compiles to 1.30 GiB (blocks of an eighth
    of it, 4096 rows, compile to 4.75 GiB), and a train_4k step, where each
    block carries its own checkpoint, to 0.82 GiB.  There the step does at
    most 0.7 of the one-block core's FLOPs."""
    S = SHAPES[shape].seq_len
    compiled = _compile_attention(one_chip, B, S, train=train)
    assert _hbm_bytes(compiled) <= limit_gib * 2**30, _hbm_bytes(compiled) / 2**30
    if train:
        full = _compile_attention(one_chip, B, S, train=True, q_block=S)
        assert _flops(compiled) <= 0.7 * _flops(full), (_flops(compiled), _flops(full))


@pytest.fixture(scope="module")
def one_node_step(topo):
    """The chip smoke's step: qwen3-0.6b at published widths, DecentLaM on
    flat planes with the Pallas update, one node on one chip, at the
    smoke's sequence length and batch, compiled once for the tests below.
    The chip packs planes with ``concat`` (the CPU takes ``gather``), so the
    compile does too."""
    smoke = _chip_smoke()
    args = train.parse_args(smoke.step_argv("pallas"))
    cfg, tcfg = train.model_config(args), train.train_config(args)
    with pytest.MonkeyPatch.context() as mp, _persistent_cache_off():
        mp.setattr(PlaneLayout, "pack",
                   functools.partialmethod(PlaneLayout.pack, impl="concat"))
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
        return step.lower(state, batch).compile()


def test_one_node_train_step_fits_one_chip(one_node_step):
    ma = one_node_step.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 16 GiB"
    assert _custom_calls(one_node_step) > 0


def test_one_node_train_step_has_no_plane_wide_select(one_node_step):
    """The finite guard lives in the update kernels: outside them the
    compiled step holds no ``select`` over an f32 plane bucket (the guard's
    restore of the momentum plane was one, a whole plane pass a step)."""
    import re

    rows = model_plane_layout(QWEN3).rows.values()
    shapes = "|".join(rf"(?:1,)?{r},{LANES}" for r in rows)
    pattern = re.compile(rf"= f32\[(?:{shapes})\]\S* select\(")
    assert not pattern.findall(one_node_step.as_text())


def test_benchmark_finds_both_update_stages(one_node_step, monkeypatch):
    """The benchmark's rule for the update kernel (``bench/harness.py``
    ``Program.kernel_names``, which reads only the compiled step) finds
    DecentLaM's two plane stages, by their ``fused_update_*`` names, and
    nothing else."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "bench"))
    import harness

    names = harness.Program.kernel_names(SimpleNamespace(compiled=one_node_step))
    assert len(names) == _custom_calls(one_node_step) == 2
    assert sorted(n.rsplit(".", 1)[0] for n in names) == [
        "fused_update_post_decentlam_post", "fused_update_pre_grad_step"]


@pytest.fixture(scope="module")
def granite_step(topo):
    """The benchmark's granite cell compiled for one v5e: granite-3.0-1b-a400m
    at published widths, 8 layers, dropless, seq 2048 x batch 4, DecentLaM
    on flat planes with the Pallas update, built from the cell's own files
    as ``bench/harness.py`` builds it."""
    import json
    import sys

    sys.path.insert(0, os.path.join(ROOT, "bench"))
    try:
        import harness
    finally:
        sys.path.pop(0)
    with open(os.path.join(ROOT, "bench", "configs", "granite-3.0-1b-a400m.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "traffic", "1node.s2048.b4.json")) as f:
        traffic = json.load(f)
    mc, tcfg = harness.model_config(cfg), harness.train_config(cfg, traffic)
    with pytest.MonkeyPatch.context() as mp, _persistent_cache_off():
        mp.setattr(PlaneLayout, "pack",
                   functools.partialmethod(PlaneLayout.pack, impl="concat"))
        mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                    axis_types=(AxisType.Auto,) * 2)
        step, sspecs, bspecs, channel = build_train_step(mc, tcfg, mesh,
                                                         node_axes=("data",))
        state = abstract_train_state(
            mc, make_optimizer(tcfg.opt_config()), 1, 1, channel=channel,
            plane_layout=model_plane_layout(mc),
        )
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=NamedSharding(mesh, s)),
            state, sspecs,
        )
        shape = (traffic["per_node_batch"], traffic["seq_len"])
        batch = {
            k: jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, bspecs[k]))
            for k in ("tokens", "targets")
        }
        return step.lower(state, batch).compile()


def test_granite_step_fits_one_chip_with_grouped_expert_kernels(granite_step):
    """The dropless granite step fits one chip (at most 15 GiB by
    ``memory_analysis``), runs its expert products in the named grouped
    kernels (forward, dlhs and drhs), and holds no expert buffer padded to
    a capacity, ``(32, C, 1024)``, as the capacity tables had (the only
    such shape left is ``w_out``'s ``(32, 512, 1024)``)."""
    import re

    gib = _hbm_bytes(granite_step) / 2**30
    assert gib <= 15.0, f"{gib:.2f} GiB"
    text = granite_step.as_text()
    kernels = set(re.findall(r"%(moe_gmm_(?:fwd|dlhs|drhs))[.\d]* = ", text))
    assert kernels == {"moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"}, kernels
    assert set(re.findall(r"\[32,(\d+),1024\]", text)) == {"512"}
