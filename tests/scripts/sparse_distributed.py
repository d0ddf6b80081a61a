"""Subprocess worker: sparse mesh channels vs their dense parents.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
pytest wrapper).  Pins, on a real shard_map mesh with per-step jit:

A. **all-dirty == dense** for every algorithm: when the grads touch every
   row, :class:`SparsePpermuteChannel` (exact + delta, plain + int8) and
   :class:`SparseDelayedPpermuteChannel` reproduce their dense parents'
   trajectories — bit-for-bit up to XLA's per-program FMA contraction:
   the sparse apply is a different XLA program (mask psum + selects), and
   the CPU backend may contract the mix's ``out + w * recv`` into an FMA
   in one program and not the other, a ≤1-ulp scheduling artifact.  Most
   algorithms land exactly equal; the pin is ``err <= 1e-6`` here, with
   the structural bitwise claim pinned on the stacked layout (identical
   arithmetic programs — tests/test_sparse_gossip.py, all 11 algorithms)
   and exact-zero end-to-end on the production train step
   (distributed_equivalence.py "sparse" mode).
B. **partial masks**: the mesh exact channel matches the stacked exact
   channel's trajectory (allclose — the two layouts order the mix FMAs
   differently), clean rows keep their initial bits, and the accounting
   reports a real saving.  Same for delay-2 exact and for delta.

Each step is its own jitted call (the harness idiom): unrolling several
steps into ONE trace lets XLA reorder FMAs around the selects and costs
bit-exactness — that is scheduling, not semantics, and the train step
never does it.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (
    ALGORITHMS,
    DelayedPpermuteChannel,
    OptimizerConfig,
    PpermuteChannel,
    build_topology,
    make_linear_regression,
    make_optimizer,
    make_psum_mean,
    make_stacked_mean,
)
from repro.launch.mesh import make_mesh
from repro.sparse import (
    SparseDelayedPpermuteChannel,
    SparsePpermuteChannel,
    SparseStackedChannel,
    grad_row_masks,
)

N, D, M = 8, 6, 10
LR = 1e-2

mesh = make_mesh((N,), ("data",))
prob = make_linear_regression(n=N, m=M, d=D, noise=0.01, seed=3, heterogeneity=1.0)
topo = build_topology("ring", N)
mean = make_psum_mean(("data",), N)

RNG = np.random.default_rng(11)
X0 = jnp.asarray(RNG.standard_normal(D), jnp.float32)  # consensus init
PARTIAL = jnp.asarray(np.arange(D) % 3 == 0)  # static touched-row set


def run_mesh(opt, channel, n_steps, *, mask=None, x0=None):
    """Per-step-jitted shard_map trajectory; returns (params, chstate)."""
    sparse = hasattr(channel, "mark")

    def body(st, Al, bl):
        x = st["x"][0]
        s = jax.tree.map(lambda a: a[0], st["opt"])
        ch = jax.tree.map(lambda a: a[0], st["ch"])
        A0, b0 = Al[0], bl[0]
        g = A0.T @ (A0 @ x - b0)
        if mask is not None:
            g = jnp.where(mask, g, 0.0)
        if sparse:
            ch = channel.mark(ch, jnp.abs(g) > 0)
        x, s, ch = opt.step(
            x, g, s, lr=jnp.float32(LR), step_idx=st["k"], gossip=channel,
            mean=mean, comp_state=ch,
        )
        return {
            "x": x[None],
            "opt": jax.tree.map(lambda a: a[None], s),
            "ch": jax.tree.map(lambda a: a[None], ch),
            "k": st["k"] + 1,
        }

    def specs(tree):
        return jax.tree.map(lambda a: P("data", *([None] * (a.ndim - 1))), tree)

    xs = jnp.broadcast_to((X0 if x0 is None else x0)[None], (N, D))
    s0 = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape),
        opt.init(jnp.zeros((D,), jnp.float32)),
    )
    ch0 = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape),
        channel.init(jnp.zeros((D,), jnp.float32)),
    )
    state = {"x": xs, "opt": s0, "ch": ch0, "k": jnp.int32(0)}
    sspecs = {"x": specs(xs), "opt": specs(s0), "ch": specs(ch0), "k": P()}
    dspecs = (P("data", None, None), P("data", None))
    step_sm = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(sspecs, *dspecs), out_specs=sspecs,
        axis_names={"data"},
    ))
    Ad = jax.device_put(prob.A, NamedSharding(mesh, dspecs[0]))
    bd = jax.device_put(prob.b, NamedSharding(mesh, dspecs[1]))
    for _ in range(n_steps):
        state = step_sm(state, Ad, bd)
    return np.asarray(state["x"]), jax.device_get(state["ch"])


def run_stacked(opt, channel, n_steps, *, mask=None):
    """The stacked-layout reference trajectory for part B."""

    @jax.jit
    def one(params, s, ch, k):
        g = prob.grad(params)
        if mask is not None:
            g = jnp.where(mask[None], g, 0.0)
        ch = channel.mark(ch, grad_row_masks(g))
        return opt.step(
            params, g, s, lr=jnp.float32(LR), step_idx=k, gossip=channel,
            mean=make_stacked_mean(N), comp_state=ch,
        )

    params = jnp.broadcast_to(X0[None], (N, D))
    s = opt.init(params)
    ch = channel.init(params)
    for k in range(n_steps):
        params, s, ch = one(params, s, ch, jnp.int32(k))
    return np.asarray(params), jax.device_get(ch)


# --- A: all-dirty bit-exactness against the dense parents -------------------

STEPS_A = 3
errs = {"exact": 0.0, "delta": 0.0}
for algorithm in ALGORITHMS:
    opt = make_optimizer(OptimizerConfig(algorithm=algorithm, momentum=0.8))
    cps = opt.gossips_per_step
    ref, _ = run_mesh(opt, PpermuteChannel(topo, ("data",)), STEPS_A)
    for label, ch in [
        ("exact", SparsePpermuteChannel(
            topo, ("data",), calls_per_step=cps)),
        ("delta", SparsePpermuteChannel(
            topo, ("data",), mode="delta", calls_per_step=cps)),
    ]:
        got, chst = run_mesh(opt, ch, STEPS_A)
        err = float(np.max(np.abs(got - ref)))
        assert err <= 1e-6, (algorithm, label, err)
        vol = chst["rows"]["vol"]
        assert np.allclose(vol["sparse"], vol["dense"], rtol=1e-6), (
            algorithm, label, vol)
        errs[label] = max(errs[label], err)
    print(f"A {algorithm}: OK (exact + delta == dense, dense-equiv bytes)")

print(f"A worst-case drift: {errs} (<= 1-2 ulp of the trajectory scale)")

opt = make_optimizer(OptimizerConfig(algorithm="decentlam", momentum=0.8))
ref, _ = run_mesh(opt, PpermuteChannel(topo, ("data",), compression="int8"), STEPS_A)
got, _ = run_mesh(
    opt, SparsePpermuteChannel(topo, ("data",), compression="int8"), STEPS_A
)
assert float(np.max(np.abs(got - ref))) <= 1e-6
print("A int8: OK")

ref, _ = run_mesh(opt, DelayedPpermuteChannel(topo, ("data",), 2), 6)
got, _ = run_mesh(opt, SparseDelayedPpermuteChannel(topo, ("data",), 2), 6)
assert float(np.max(np.abs(got - ref))) <= 1e-6
print("A delayed(2): OK")

# --- B: partial masks — mesh vs stacked, frozen clean rows, real savings ----

STEPS_B = 6
clean = ~np.asarray(PARTIAL)
for label, mk_mesh, mk_stack in [
    ("exact", lambda: SparsePpermuteChannel(topo, ("data",)),
     lambda: SparseStackedChannel(topo)),
    ("delta", lambda: SparsePpermuteChannel(topo, ("data",), mode="delta"),
     lambda: SparseStackedChannel(topo, mode="delta")),
    ("exact-delay2",
     lambda: SparseDelayedPpermuteChannel(topo, ("data",), 2),
     lambda: SparseStackedChannel(topo, 2)),
]:
    got, chst = run_mesh(opt, mk_mesh(), STEPS_B, mask=PARTIAL)
    ref, _ = run_stacked(opt, mk_stack(), STEPS_B, mask=PARTIAL)
    err = float(np.max(np.abs(got - ref)))
    assert np.allclose(got, ref, atol=1e-4), (label, err)
    # untouched rows never ship and never move: initial bits preserved
    assert np.array_equal(got[:, clean], np.broadcast_to(
        np.asarray(X0)[clean][None], (N, clean.sum()))), label
    vol = chst["rows"]["vol"]
    assert float(np.mean(vol["sparse"])) < 0.75 * float(np.mean(vol["dense"])), (
        label, vol)
    print(f"B {label}: OK maxerr={err:.2e} sparse/dense="
          f"{float(np.mean(vol['sparse'])) / float(np.mean(vol['dense'])):.2f}")

# --- C: collective-count accounting ----------------------------------------

payload = {"w": jnp.zeros((D,), jnp.float32)}
dense_cpr = PpermuteChannel(topo, ("data",)).collectives_per_round(payload)
exact_ch = SparsePpermuteChannel(topo, ("data",))
delta_ch = SparsePpermuteChannel(topo, ("data",), mode="delta")
assert exact_ch.collectives_per_round(payload) == dense_cpr + 1  # mask psum
assert delta_ch.collectives_per_round(payload) == dense_cpr + 2  # mask/class
print("C collectives: OK")

print(f"sparse-distributed: OK ({len(ALGORITHMS)} algorithms + 2 + 3 + 1 cases)")
