"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles,
interpret=True on CPU (the kernels' TPU lowering path is exercised on real
hardware; the *math* is identical)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import StackedChannel, build_topology, make_stacked_mean
from repro.core.optimizers import ALGORITHMS, OptimizerConfig, make_optimizer
from repro.core.planes import PlaneLayout, plane_scalars
from repro.core.update_spec import MathCtx, reference_stage, run_update, update_spec
from repro.kernels.fused_update import make_plane_stage
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import reference_attention
from repro.kernels.mlstm_chunk.ops import mlstm
from repro.kernels.mlstm_chunk.ref import (
    mlstm_chunked,
    mlstm_decode_step,
    mlstm_sequential,
)

RNG = np.random.default_rng(0)


def _rand(shape, dt):
    return jnp.asarray(RNG.standard_normal(shape), dt)


FLASH_CASES = [
    # B, Sq, Sk, H, Hkv, hd, causal, window, dtype
    (2, 128, 128, 4, 2, 64, True, 0, jnp.float32),
    (1, 256, 256, 4, 4, 32, True, 64, jnp.float32),
    (2, 100, 100, 2, 1, 64, True, 0, jnp.bfloat16),
    (1, 128, 128, 2, 2, 64, False, 0, jnp.float32),
    (1, 64, 192, 2, 2, 64, False, 0, jnp.float32),  # cross-ish Sq != Sk
    (2, 160, 160, 8, 2, 32, True, 96, jnp.bfloat16),
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_reference(case):
    B, Sq, Sk, H, Hkv, hd, causal, window, dt = case
    q = _rand((B, Sq, H, hd), dt)
    k = _rand((B, Sk, Hkv, hd), dt)
    v = _rand((B, Sk, Hkv, hd), dt)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    ref = reference_attention(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_flash_attention_block_size_invariance():
    q = _rand((1, 256, 2, 64), jnp.float32)
    k = _rand((1, 256, 2, 64), jnp.float32)
    v = _rand((1, 256, 2, 64), jnp.float32)
    outs = [
        np.asarray(
            flash_attention(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
        )
        for (bq, bk) in [(64, 64), (128, 64), (64, 128), (128, 128)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5)


MLSTM_CASES = [
    (2, 3, 128, 32, 48, 32),
    (1, 2, 256, 64, 64, 64),
    (1, 1, 64, 16, 16, 64),
]


@pytest.mark.parametrize("case", MLSTM_CASES)
def test_mlstm_chunked_matches_sequential(case):
    B, H, S, dk, dv, chunk = case
    q, k = _rand((B, H, S, dk), jnp.float32), _rand((B, H, S, dk), jnp.float32)
    v = _rand((B, H, S, dv), jnp.float32)
    ir = _rand((B, H, S), jnp.float32)
    fr = 2.0 + _rand((B, H, S), jnp.float32)
    h_seq, st_seq = mlstm_sequential(q, k, v, ir, fr)
    h_ch, st_ch = mlstm_chunked(q, k, v, ir, fr, chunk=chunk)
    np.testing.assert_allclose(np.asarray(h_seq), np.asarray(h_ch), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(st_seq["C"]), np.asarray(st_ch["C"]), atol=2e-4
    )


@pytest.mark.parametrize("case", MLSTM_CASES[:2])
def test_mlstm_pallas_matches_ref(case):
    B, H, S, dk, dv, chunk = case
    q, k = _rand((B, H, S, dk), jnp.float32), _rand((B, H, S, dk), jnp.float32)
    v = _rand((B, H, S, dv), jnp.float32)
    ir = _rand((B, H, S), jnp.float32)
    fr = 2.0 + _rand((B, H, S), jnp.float32)
    h_ref, st_ref = mlstm(q, k, v, ir, fr, chunk=chunk, impl="ref")
    h_pl, st_pl = mlstm(q, k, v, ir, fr, chunk=chunk, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(h_ref), np.asarray(h_pl), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(st_ref["C"]), np.asarray(st_pl["C"]), atol=2e-4
    )


def test_mlstm_decode_extends_sequence():
    B, H, S, dk, dv = 1, 2, 65, 32, 32
    q, k = _rand((B, H, S, dk), jnp.float32), _rand((B, H, S, dk), jnp.float32)
    v = _rand((B, H, S, dv), jnp.float32)
    ir = _rand((B, H, S), jnp.float32)
    fr = 2.0 + _rand((B, H, S), jnp.float32)
    h_all, _ = mlstm_sequential(q, k, v, ir, fr)
    _, st = mlstm_chunked(
        q[:, :, : S - 1], k[:, :, : S - 1], v[:, :, : S - 1],
        ir[:, :, : S - 1], fr[:, :, : S - 1], chunk=16,
    )
    h1, _ = mlstm_decode_step(
        q[:, :, S - 1], k[:, :, S - 1], v[:, :, S - 1],
        ir[:, :, S - 1], fr[:, :, S - 1], st,
    )
    np.testing.assert_allclose(
        np.asarray(h1), np.asarray(h_all[:, :, S - 1]), atol=2e-4
    )


def _plane_stage_on_tree(kind, op, ctx, ops, scalars, like_x):
    """The Pallas plane stage (interpret mode) on trees: each operand is
    packed into the plane layout of ``like_x``, the stage runs once per
    bucket, and its outputs are unpacked to trees again."""
    lay = PlaneLayout.build(like_x)
    packed = {
        n: lay.pack(t, dtype=None if n == "x" else jnp.float32)
        for n, t in ops.items()
    }
    out = make_plane_stage("pallas_interpret")(
        kind, op, ctx, packed, scalars, lay.pack(like_x)
    )
    return {
        n: lay.unpack(v, like=like_x) if n == "x"
        else lay.unpack(v, dtype=jnp.float32)
        for n, v in out.items()
    }


def _decentlam_post(x, mix, m, lr, beta, stage):
    """The DecentLaM tail (eq. 17 + momentum + step) as one post stage:
    ``g~ = (x - mix) / lr``, ``m_new = beta m + g~``, ``x_new = x - lr
    m_new``.  Returns ``(x_new, m_new)``."""
    scalars = {"lr": jnp.float32(lr), "gs": jnp.float32(1.0),
               "r": jnp.float32(1.0)}
    out = stage("post", "decentlam_post", MathCtx(beta=beta),
                {"x": {"w": x}, "mix": {"w": mix}, "m": {"w": m}}, scalars,
                {"w": x})
    return out["x"]["w"], out["m"]["w"]


@pytest.mark.parametrize(
    "shape,dt",
    [((1000,), jnp.float32), ((33, 77), jnp.float32), ((8, 128), jnp.bfloat16),
     ((64, 1024), jnp.float32)],
)
def test_decentlam_update_kernel(shape, dt):
    x = _rand(shape, dt)
    mix = x - 0.01 * jnp.sign(x)
    m = _rand(shape, jnp.float32)
    lr = 0.02
    p_ref, m_ref = _decentlam_post(x, mix, m, lr, 0.9, reference_stage)
    p_pl, m_pl = _decentlam_post(x, mix, m, lr, 0.9, _plane_stage_on_tree)
    np.testing.assert_allclose(
        np.asarray(p_ref, np.float32), np.asarray(p_pl, np.float32),
        atol=1e-2 if dt == jnp.bfloat16 else 1e-6,
    )
    np.testing.assert_allclose(np.asarray(m_ref), np.asarray(m_pl), atol=1e-2)


@pytest.mark.parametrize("sg", [1.0, 0.37, 0.0])
def test_decentlam_sa_post_fused_matches_reference(sg):
    """Fused-vs-reference parity for the staleness-aware op at real damping
    factors (the fused engine receives sg as the 4th SMEM scalar — the
    per-node value inside shard_map).  At sg=1 both must also equal the
    plain decentlam_post (the bit-exactness hinge)."""
    rng = np.random.default_rng(17)
    ops = {
        name: {"w": jnp.asarray(rng.standard_normal((9, 33)), jnp.float32)}
        for name in ("x", "mix", "m", "g")
    }
    scalars = {
        "lr": jnp.float32(0.02),
        "gs": jnp.float32(1.0),
        "r": jnp.float32(1.0),
        "sg": jnp.float32(sg),
    }
    ctx = MathCtx(beta=0.9)
    ref = reference_stage(
        "post", "decentlam_sa_post", ctx, ops, scalars, ops["x"]
    )
    fus = _plane_stage_on_tree(
        "post", "decentlam_sa_post", ctx, ops, scalars, ops["x"]
    )
    for k in ("x", "m"):
        np.testing.assert_allclose(
            np.asarray(ref[k]["w"]), np.asarray(fus[k]["w"]), rtol=1e-5,
            atol=1e-5, err_msg=k,
        )
    if sg == 1.0:
        plain = reference_stage(
            "post", "decentlam_post", ctx,
            {k: ops[k] for k in ("x", "mix", "m")}, scalars, ops["x"],
        )
        for k in ("x", "m"):
            np.testing.assert_array_equal(
                np.asarray(ref[k]["w"]), np.asarray(plain[k]["w"])
            )


def test_decentlam_update_semantics():
    """x_new must equal mix - lr*beta*m (algebraic identity of eq. 17 tail)."""
    x = _rand((256,), jnp.float32)
    mix = _rand((256,), jnp.float32)
    m = _rand((256,), jnp.float32)
    p, _ = _decentlam_post(x, mix, m, 0.1, 0.9, reference_stage)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(mix - 0.1 * 0.9 * m), atol=1e-5
    )


# ---------------------------------------------------------------------------
# Fused multi-algorithm engine: every algorithm's full update tail through
# the Pallas plane stage kernels (interpret mode, per node over the stacked
# planes) vs the stacked reference step.
# ---------------------------------------------------------------------------

N_NODES = 8


def _per_node(stage):
    """``stage`` run on each node's planes of stacked ``(n, rows, LANES)``
    buffers; the stage scalars are shared by every node."""

    def run(kind, op, ctx, operands, scalars, like_x):
        def one(ops, like):
            return stage(kind, op, ctx, ops, scalars, like)

        return jax.vmap(one)(operands, like_x)

    return run


def _fused_vs_reference(cfg: OptimizerConfig, dt, *, steps=1, lr=0.01):
    """Run `steps` of the stacked harness via both paths and compare."""
    rng = np.random.default_rng(7)
    topo = build_topology("exp", N_NODES)
    gossip, mean = StackedChannel(topo), make_stacked_mean(N_NODES)
    params = {
        "w": jnp.asarray(rng.standard_normal((N_NODES, 37)), dt),
        "b": jnp.asarray(rng.standard_normal((N_NODES, 5, 3)), dt),
    }
    opt = make_optimizer(cfg)
    spec = update_spec(cfg)
    stage = _per_node(make_plane_stage("pallas_interpret"))
    lay = PlaneLayout.build(jax.tree.map(lambda a: a[0], params))

    p_ref, p_fus = params, lay.pack(params, leading=1)
    s_ref = opt.init(params)
    s_fus = {k: lay.pack(v, dtype=jnp.float32, leading=1)
             for k, v in s_ref.items()}
    for k in range(steps):
        grads = {
            kk: jnp.asarray(rng.standard_normal(v.shape), jnp.float32)
            for kk, v in params.items()
        }
        p_ref, s_ref, _ = opt.step(
            p_ref, grads, s_ref, lr=lr, step_idx=jnp.int32(k),
            gossip=gossip, mean=mean,
        )
        # the scalars come from the stacked trees, as the reference's do
        scalars = plane_scalars(
            cfg, lay, lay.unpack(p_fus, like=params, leading=1), grads
        )
        x, s_fus, _ = run_update(
            spec, cfg, x=p_fus, g=lay.pack(grads, dtype=jnp.float32, leading=1),
            state=s_fus, lr=lr, step_idx=jnp.int32(k), gossip=gossip,
            mean=mean, comp_state=(), stage=stage, scalars=scalars,
        )
        p_fus = jax.tree.map(lambda p, v: v.astype(p.dtype), p_fus, x)
    p_fus = lay.unpack(p_fus, like=params, leading=1)
    s_fus = {k: lay.unpack(v, dtype=jnp.float32, leading=1)
             for k, v in s_fus.items()}

    # the momentum recovery (x - mix)/lr amplifies roundoff by 1/lr per
    # step, so state comparisons need a relative component
    tol = 4e-2 if dt == jnp.bfloat16 else 2e-5
    rtol = 2e-3
    for kk in params:
        np.testing.assert_allclose(
            np.asarray(p_ref[kk], np.float32),
            np.asarray(p_fus[kk], np.float32),
            rtol=rtol,
            atol=tol,
            err_msg=f"{cfg.algorithm} params[{kk}]",
        )
    for sk in s_ref:
        for kk in params:
            np.testing.assert_allclose(
                np.asarray(s_ref[sk][kk], np.float32),
                np.asarray(s_fus[sk][kk], np.float32),
                rtol=rtol,
                atol=tol,
                err_msg=f"{cfg.algorithm} state[{sk}][{kk}]",
            )


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("algo", ALGORITHMS)
def test_fused_engine_matches_reference(algo, dt):
    cfg = OptimizerConfig(
        algorithm=algo, momentum=0.9, weight_decay=0.01, slowmo_period=2
    )
    _fused_vs_reference(cfg, dt, steps=2)


@pytest.mark.parametrize(
    "cfg",
    [
        OptimizerConfig(algorithm="decentlam", momentum=0.9, nesterov=True),
        OptimizerConfig(algorithm="dmsgd", momentum=0.9, nesterov=True,
                        weight_decay=0.1, decoupled_wd=True),
        OptimizerConfig(algorithm="decentlam", momentum=0.9, grad_clip=0.5),
        OptimizerConfig(algorithm="pmsgd-lars", momentum=0.9,
                        weight_decay=1e-4, lars_trust=0.02),
        OptimizerConfig(algorithm="dmsgd", momentum=0.9, lars=True,
                        weight_decay=1e-4, grad_clip=1.0),
        OptimizerConfig(algorithm="da-dmsgd", momentum=0.9, weight_decay=0.1,
                        decoupled_wd=True),
    ],
    ids=["nesterov", "nesterov-decoupled-wd", "clip", "lars", "lars-clip",
         "two-gossip-decoupled-wd"],
)
def test_fused_engine_feature_flags(cfg):
    """Nesterov / weight decay / clip / LARS fold into the fused stages."""
    _fused_vs_reference(cfg, jnp.float32, steps=2)
