"""The expert layer's sorted dispatch and its grouped-matmul kernel, on the CPU.

The kernel (``kernels/moe_gmm``) runs in Pallas interpret mode and is held
to its jnp oracle (``ref.py``); the layer (``models/moe.py``) is held to a
dense reference written here: every expert over every token, weighted by
the combination matrix of the routing.  All in float32 with seeded weights.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.moe_gmm.ops import moe_gmm, tiling
from repro.kernels.moe_gmm.ref import gmm_ref, tgmm_ref
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.layers import Initializer, TPContext

ROOT = os.path.join(os.path.dirname(__file__), "..")
TP1 = TPContext()
# float32 sums in another order: the kernel accumulates tile by tile, the
# oracles in one einsum
TOL = dict(rtol=2e-5, atol=2e-5)

GROUP_SIZES = {
    "ragged": [13, 0, 40, 7, 0, 36],
    "half_in_one": [4, 50, 10, 0, 18, 18],  # 50 of the 100 rows in one group
    "first_and_last_empty": [0, 33, 33, 34, 0, 0],
}


def _rand(key, shape, i):
    return jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)


@pytest.mark.parametrize("sizes", list(GROUP_SIZES), ids=list(GROUP_SIZES))
def test_kernel_matches_oracle(sizes):
    """Forward, ``dlhs`` and ``drhs`` at ragged sizes, on 100 rows in tiles
    of 112, 6 groups, k 64, n 32; and again for groups 2-3 alone (the
    expert-parallel offset), where rows of other groups come out 0."""
    gs = jnp.asarray(GROUP_SIZES[sizes], jnp.int32)
    key = jax.random.key(7)
    m, k, n = int(gs.sum()), 64, 32
    lhs, rhs, ct = _rand(key, (m, k), 0), _rand(key, (6, k, n), 1), _rand(key, (m, n), 2)
    for off, w in ((0, rhs), (2, rhs[2:4])):
        out, vjp = jax.vjp(lambda a, b: moe_gmm(a, b, gs, off), lhs, w)
        dlhs, drhs = vjp(ct)
        np.testing.assert_allclose(out, gmm_ref(lhs, w, gs, off), **TOL)
        np.testing.assert_allclose(
            dlhs, gmm_ref(ct, w, gs, off, transpose_rhs=True), **TOL)
        np.testing.assert_allclose(
            drhs, tgmm_ref(lhs, ct, gs, off, num_groups=w.shape[0]), **TOL)


def test_kernel_tiles():
    """Whole k and n, 512-row tiles, small m rounded to 16."""
    assert tiling(65536, 1024, 512) == (512, 1024, 512)
    assert tiling(100, 64, 32) == (112, 64, 32)


# ---------------------------------------------------------------------------
# the layer against a dense reference
# ---------------------------------------------------------------------------


def _cfg(capacity_factor, **kw):
    base = get_config("granite-moe-1b-a400m", smoke=True)  # 4 experts, top-2
    return dataclasses.replace(base, capacity_factor=capacity_factor, **kw)


def _params(cfg, hot: bool):
    """Seeded expert weights; ``hot`` sends every token's first choice to
    expert 0 (the inputs carry +3 on feature 0, which expert 0's router
    column weighs by 10)."""
    p = moe_mod.moe_init(Initializer(jax.random.key(3)), cfg)
    if hot:
        p["router"] = p["router"].at[0, 0].set(10.0)
    return p


def _inputs(cfg, B=2, S=32):
    x = jax.random.normal(jax.random.key(5), (B, S, cfg.d_model), jnp.float32)
    return x.at[..., 0].add(3.0)


def _parent_keep(expert_idx, E, C):
    """Capacity in token order, as the capacity-table layer had it: an
    assignment's slot is its running count among its expert's assignments
    (the flat (T, k) order); slots past C are dropped."""
    flat = expert_idx.reshape(-1)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    return (pos < C).reshape(expert_idx.shape)


def dense_moe(x, p, cfg, C=None):
    """Every expert over every token, weighted by the combination matrix.
    Returns (out, aux losses, kept assignments (T, k))."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, d)
    T_ = xt.shape[0]
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    keep = jnp.ones_like(idx, bool) if C is None else _parent_keep(idx, E, C)
    comb = jnp.zeros((T_, E)).at[jnp.arange(T_)[:, None], idx].add(
        jnp.where(keep, gates, 0.0))
    ys = jnp.stack([
        (jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_in"][e])) @ p["w_out"][e]
        for e in range(E)])
    out = jnp.einsum("te,etd->td", comb, ys)
    chosen = jnp.sum(jax.nn.one_hot(idx, E), axis=1)
    lb = E * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(chosen, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return out.reshape(B, S, d), (lb, z), keep


def _layer(x, p, cfg):
    out, aux = moe_mod.moe_forward(x, p, cfg, TP1)
    return out, aux


@pytest.mark.parametrize("hot", [True, False], ids=["hot_expert", "even"])
def test_dropless_layer_matches_dense_reference(hot):
    """At capacity_factor = E/k every assignment is kept, also when one
    expert takes half of them; outputs, aux losses and gradients (inputs and
    every weight) match the dense reference."""
    cfg = _cfg(4 / 2)
    assert moe_mod.moe_capacity(cfg, 64) >= 64
    p, x = _params(cfg, hot), _inputs(cfg)
    out, aux = _layer(x, p, cfg)
    ref, (lb, z), _ = dense_moe(x, p, cfg)
    np.testing.assert_allclose(out, ref, **TOL)
    np.testing.assert_allclose(aux["moe_load_balance"], lb, rtol=1e-6)
    np.testing.assert_allclose(aux["moe_router_z"], z, rtol=1e-6)
    assert float(aux["moe_dropped"]) == 0.0
    share = float(aux["moe_max_load"]) / cfg.n_experts
    assert (share >= 0.5) == hot, share  # the busiest expert's share of rows

    ct = _rand(jax.random.key(9), x.shape, 0)
    g = jax.grad(lambda x, p: jnp.sum(_layer(x, p, cfg)[0] * ct), (0, 1))(x, p)
    gr = jax.grad(lambda x, p: jnp.sum(dense_moe(x, p, cfg)[0] * ct), (0, 1))(x, p)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_capacity_drops_as_the_capacity_tables_did():
    """At 1.25 the hot expert overflows: the same assignments are dropped
    as by running counts in token order, the outputs and gradients are the
    dense reference's with those gates zeroed, ``moe_dropped`` counts the
    drops and ``moe_expert_hits`` marks the experts a kept row reaches."""
    cfg = _cfg(1.25)
    p, x = _params(cfg, True), _inputs(cfg)
    C = moe_mod.moe_capacity(cfg, 64)
    out, aux = _layer(x, p, cfg)
    ref, _, keep = dense_moe(x, p, cfg, C)
    n_drop = int(jnp.sum(~keep))
    assert n_drop > 0
    assert float(aux["moe_dropped"]) == n_drop
    np.testing.assert_allclose(out, ref, **TOL)
    probs = jax.nn.softmax(x.reshape(64, -1) @ p["router"], axis=-1)
    idx = jax.lax.top_k(probs, cfg.top_k)[1]
    hits = jnp.zeros(cfg.n_experts).at[jnp.where(keep, idx, cfg.n_experts)].max(
        1.0, mode="drop")
    np.testing.assert_array_equal(aux["moe_expert_hits"], hits)

    ct = _rand(jax.random.key(9), x.shape, 0)
    g = jax.grad(lambda p: jnp.sum(_layer(x, p, cfg)[0] * ct))(p)
    gr = jax.grad(lambda p: jnp.sum(dense_moe(x, p, cfg, C)[0] * ct))(p)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_step_counters_reach_the_loss_metrics():
    """``forward_loss`` reports ``moe_dropped`` (summed over the layers) and
    ``moe_max_load`` (maxed), 0 dropped when dropless; a dense model reports
    neither, so its step's metrics are as they were."""
    rt = T.RuntimeConfig(dtype="float32", remat=False)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 8)  # few ids: hot experts
    batch = {"tokens": tokens, "targets": tokens}
    params = T.init_params(jax.random.key(0), _cfg(1.25))
    for cf in (1.25, 2.0):
        _, met = T.forward_loss(params, batch, _cfg(cf), TP1, rt)
        assert float(met["moe_max_load"]) >= 1.0
        assert (float(met["moe_dropped"]) == 0.0) == (cf == 2.0)
    dense = get_config("qwen3-0.6b", smoke=True)
    _, met = T.forward_loss(T.init_params(jax.random.key(0), dense), batch, dense, TP1, rt)
    assert "moe_dropped" not in met and "moe_max_load" not in met


def test_tensor_parallel_modes_sum_to_the_uncut_layer():
    """On two CPU devices, the expert mode (4 experts, 2 a device) and the
    ffn mode (3 experts, d_ff cut in half) each give the uncut layer's
    output and gradients after their one psum."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "tests", "scripts", "moe_tp.py")],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "expert: OK" in proc.stdout and "ffn: OK" in proc.stdout, proc.stdout


# ---------------------------------------------------------------------------
# the granite SMOKE model against the benchmark's reference
# ---------------------------------------------------------------------------


def test_granite_smoke_loss_and_grads_match_benchmark_reference(monkeypatch):
    """The program's loss and gradients for the granite SMOKE model at
    capacity_factor = E/k against ``bench/reference.node_loss`` in float32,
    from the same seeded weights.  Tolerance 1e-4 of each leaf's largest
    gradient entry: both sides are float32, but they sum in other orders
    (sorted rows and the kernel's tiles against a dense loop over experts,
    the cross entropy in one block against blocks of rows)."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "bench"))
    import json

    import harness
    import reference
    import weights

    with open(os.path.join(ROOT, "bench", "tests", "data", "configs",
                           "granite-smoke.json")) as f:
        cfg = json.load(f)
    cfg["capacity_factor"] = cfg["num_local_experts"] / cfg["num_experts_per_tok"]
    mc = harness.model_config(cfg)
    rt = T.RuntimeConfig(dtype="float32", remat=True)
    params = weights.build_params(weights.seed_key(2**33 + 11), cfg)
    tok = jax.random.randint(jax.random.key(4), (2, 64), 0, 16)
    tgt = jnp.roll(tok, -1, axis=1)

    def program(p):
        return T.forward_loss(p, {"tokens": tok, "targets": tgt}, mc, TP1, rt)[0]

    def ref(p):
        return reference.node_loss(p, tok, tgt, cfg, reference.make_mm("f32"))

    lp, gp = jax.value_and_grad(program)(params)
    lr, gr = jax.value_and_grad(ref)(params)
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b)))
        err = float(jnp.max(jnp.abs(a - b)))
        assert err <= 1e-4 * scale, (jax.tree_util.keystr(path), err, scale)
