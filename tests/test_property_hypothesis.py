"""Property-based tests (hypothesis) on the system's invariants.

Requires the ``test`` extra (``pip install -e .[test]``); the module skips
cleanly when hypothesis isn't installed so bare-environment collection
still works.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="install the [test] extra")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.topology import metropolis_weights, rho, _classes_from_W  # noqa: E402
from repro.core import (  # noqa: E402
    DelayedStackedChannel,
    StackedChannel,
    build_topology,
    consensus_distance,
)
from repro.core.planes import PlaneLayout  # noqa: E402
from repro.core.update_spec import MathCtx  # noqa: E402
from repro.kernels.fused_update import make_plane_stage  # noqa: E402
from repro.kernels.flash_attention.ops import flash_attention  # noqa: E402
from repro.kernels.flash_attention.ref import reference_attention  # noqa: E402

SET = settings(max_examples=25, deadline=None)


@st.composite
def connected_adjacency(draw, max_n=10):
    n = draw(st.integers(3, max_n))
    adj = np.zeros((n, n), np.int64)
    # random spanning tree guarantees connectivity
    perm = draw(st.permutations(list(range(n))))
    for i in range(1, n):
        j = perm[draw(st.integers(0, i - 1))]
        adj[perm[i], j] = adj[j, perm[i]] = 1
    # extra random edges
    extra = draw(st.integers(0, n))
    for _ in range(extra):
        a = draw(st.integers(0, n - 1))
        b = draw(st.integers(0, n - 1))
        if a != b:
            adj[a, b] = adj[b, a] = 1
    return adj


@SET
@given(connected_adjacency())
def test_metropolis_always_doubly_stochastic(adj):
    W = metropolis_weights(adj)
    n = adj.shape[0]
    np.testing.assert_allclose(W, W.T, atol=1e-12)
    np.testing.assert_allclose(W.sum(axis=1), np.ones(n), atol=1e-12)
    assert (W >= -1e-12).all()
    assert rho(W) < 1.0  # connected => mixing


@SET
@given(connected_adjacency())
def test_edge_class_decomposition_reconstructs_W(adj):
    W = metropolis_weights(adj)
    n = W.shape[0]
    R = np.diag(np.diag(W))
    for c in _classes_from_W(W):
        c.validate(n)
        for src, dst in c.pairs:
            R[dst, src] += c.recv_weight[dst]
    np.testing.assert_allclose(R, W, atol=1e-12)


@SET
@given(
    st.sampled_from(["ring", "torus", "exp", "one-peer-exp"]),
    st.integers(0, 1000),
)
def test_gossip_mean_preservation_any_step(name, step):
    topo = build_topology(name, 8)
    ch = StackedChannel(topo)
    rng = np.random.default_rng(step)
    x = jnp.asarray(rng.standard_normal((8, 7)), jnp.float32)
    _, y = ch.apply({}, x, jnp.int32(step))
    np.testing.assert_allclose(
        np.asarray(jnp.mean(y, 0)), np.asarray(jnp.mean(x, 0)), atol=1e-5
    )
    assert float(consensus_distance(y)) <= float(consensus_distance(x)) + 1e-6


@SET
@given(
    st.sampled_from(["ring", "torus", "exp", "one-peer-exp", "full"]),
    st.sampled_from([None, "bf16", "int8", "topk:0.3"]),
    st.integers(1, 4),
    st.integers(1, 4),
)
def test_delayed_channel_delay0_bitexact_and_gap_capped(name, comp, delay, steps):
    """For every topology x compressor: the delayed channel at delay=0 is
    bit-exact with the plain channel, and at delay=k the per-edge version
    gaps never exceed the configured cap (and warm up as min(k, rounds))."""
    topo = build_topology(name, 8)
    plain = StackedChannel(topo, compression=comp)
    delayed0 = DelayedStackedChannel(topo, 0, compression=comp)
    xs = [
        jnp.asarray(
            np.random.default_rng(1000 * delay + t).standard_normal((8, 5)),
            jnp.float32,
        )
        for t in range(steps)
    ]
    st_p, st_0 = plain.init(xs[0]), delayed0.init(xs[0])
    for t, x in enumerate(xs):
        st_p, y_p = plain.apply(st_p, x, jnp.int32(t))
        st_0, y_0 = delayed0.apply(st_0, x, jnp.int32(t))
        np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_0))

    delayed = DelayedStackedChannel(topo, delay, compression=comp)
    st_d = delayed.init(xs[0])
    assert int(np.max(np.asarray(delayed.version_gaps(st_d)))) == 0
    for t, x in enumerate(xs):
        st_d, _ = delayed.apply(st_d, x, jnp.int32(t))
        gaps = np.asarray(delayed.version_gaps(st_d))
        assert gaps.max() <= delay
        # round t mixed payloads exactly min(delay, t) rounds old (warmup)
        assert gaps.max() == min(delay, t)
        assert gaps.min() >= 0


@SET
@given(
    st.integers(1, 3),  # batch
    st.sampled_from([32, 64, 96]),  # seq
    st.sampled_from([1, 2, 4]),  # heads
    st.sampled_from([32, 64]),  # head dim
    st.booleans(),  # causal
    st.sampled_from([0, 16]),  # window
)
def test_flash_attention_property(b, s, h, hd, causal, window):
    rng = np.random.default_rng(b * 1000 + s + h)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    if window and not causal:
        causal = True  # windowed bidir not used by any arch
    out = flash_attention(q, k, v, causal=causal, window=window, bq=32, bk=32,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


@SET
@given(
    st.sampled_from(["ring", "torus", "exp", "one-peer-exp", "full"]),
    st.sampled_from([0, 1, 2, 4]),
    st.floats(0.05, 1.0),
)
def test_decentlam_sa_gap0_bitexact_and_damping_monotone(name, delay, base):
    """Across topology x delay k in {0,1,2,4}: at k=0 decentlam-sa is
    bit-exact with decentlam (params and momentum), at k>0 it stays finite
    where decentlam's estimator is unstable; and the damping schedule is
    exactly 1 at gap 0 and monotone non-increasing in the observed gap."""
    from repro.core import (
        OptimizerConfig,
        make_linear_regression,
        make_optimizer,
    )
    from repro.core.update_spec import staleness_damping
    from repro.sim import run_delayed

    cfg = OptimizerConfig(
        algorithm="decentlam-sa", momentum=0.8, sa_damping=base
    )
    gaps = jnp.arange(0, 9)
    f = np.asarray(staleness_damping(cfg, gaps))
    assert f[0] == 1.0
    assert (np.diff(f) <= 1e-7).all()

    topo = build_topology(name, 8)
    prob = make_linear_regression(n=8, m=6, d=5, seed=delay)
    x0 = jnp.zeros((8, 5), jnp.float32)

    def g(x, s):
        return prob.grad(x)

    p_sa, s_sa, _ = run_delayed(
        make_optimizer(cfg), topo, x0, g, delay=delay, lr=1e-2, n_steps=4
    )
    if delay == 0:
        p_dl, s_dl, _ = run_delayed(
            make_optimizer(OptimizerConfig(algorithm="decentlam", momentum=0.8)),
            topo, x0, g, delay=0, lr=1e-2, n_steps=4,
        )
        np.testing.assert_array_equal(np.asarray(p_sa), np.asarray(p_dl))
        np.testing.assert_array_equal(
            np.asarray(s_sa["m"]), np.asarray(s_dl["m"])
        )
    else:
        assert np.isfinite(np.asarray(p_sa)).all()


@SET
@given(
    st.integers(1, 2000),  # size
    st.floats(0.0, 0.99),  # beta
    st.floats(1e-6, 0.5),  # lr
)
def test_decentlam_update_identity(n, beta, lr):
    """Fused decentlam_post plane stage == x - lr*(beta*m + (x - mix)/lr)
    for any shape/params."""
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    mix = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.asarray(rng.standard_normal(n), jnp.float32)
    lay = PlaneLayout.build({"w": x})
    out = make_plane_stage("pallas_interpret")(
        "post", "decentlam_post", MathCtx(beta=beta),
        {k: lay.pack({"w": v}) for k, v in (("x", x), ("mix", mix), ("m", m))},
        {"lr": jnp.float32(lr), "gs": jnp.float32(1.0), "r": jnp.float32(1.0)},
        lay.pack({"w": x}),
    )
    p, m2 = (lay.unpack(out[k], dtype=jnp.float32) for k in ("x", "m"))
    g_tilde = (x - mix) / max(lr, 1e-12)
    m_expect = beta * m + g_tilde
    x_expect = x - lr * m_expect
    np.testing.assert_allclose(np.asarray(m2["w"]), np.asarray(m_expect), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(x_expect), rtol=2e-4, atol=2e-4)


@st.composite
def random_pytree(draw):
    """A mixed-dtype parameter pytree with random leaf shapes — nested
    dicts, 1-3D leaves, f32/bf16 buckets, sizes straddling the 1024-lane
    row boundary."""
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    n_leaves = draw(st.integers(1, 8))
    tree = {}
    for i in range(n_leaves):
        ndim = draw(st.integers(1, 3))
        shape = tuple(draw(st.integers(1, 40)) for _ in range(ndim))
        dt = draw(st.sampled_from(["float32", "bfloat16"]))
        leaf = jnp.asarray(rng.standard_normal(shape), jnp.dtype(dt))
        group = f"g{i % 3}"
        tree.setdefault(group, {})[f"p{i}"] = leaf
    return tree


@SET
@given(random_pytree(), st.sampled_from(["decentlam", "dmsgd", "pmsgd-lars",
                                         "decentlam-sa"]))
def test_plane_pack_roundtrip_and_parity_any_tree(tree, algo):
    """Flat-plane invariants over arbitrary tree shapes: (1) pack/unpack is
    a lossless round trip in both lowerings; (2) the packed update tail is
    bit-exact with the per-leaf reference tail (LARS row scalars and
    staleness damping included)."""
    import jax

    from repro.core.optimizers import OptimizerConfig, make_optimizer
    from repro.core.planes import LANES, PlaneLayout, plane_scalars
    from repro.core.update_spec import run_update, update_spec

    lay = PlaneLayout.build(tree)
    for impl in ("concat", "gather"):
        planes = lay.pack(tree, impl=impl)
        for key, buf in planes.items():
            assert buf.shape == (lay.rows[key], LANES)
        back = lay.unpack(planes, like=tree)
        assert all(
            jax.tree.leaves(
                jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), back, tree)
            )
        )

    cfg = OptimizerConfig(algorithm=algo, momentum=0.9, weight_decay=0.01,
                          grad_clip=1.0)
    spec = update_spec(cfg)
    rng = np.random.default_rng(7)
    g = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), jnp.float32), tree
    )
    state = make_optimizer(cfg).init(tree)

    def gossip(t, step, comp):
        return jax.tree.map(lambda a: 0.5 * a, t), comp

    ng = jnp.int32(1) if spec.staleness_aware else None
    kw = dict(lr=0.01, step_idx=jnp.int32(0), gossip=gossip, mean=lambda t: t,
              comp_state=(), node_gaps=ng)
    x1, s1, _ = run_update(spec, cfg, x=tree, g=g, state=state, **kw)
    x2p, s2p, _ = run_update(
        spec, cfg, x=lay.pack(tree), g=lay.pack(g, dtype=jnp.float32),
        state={k: lay.pack(v, dtype=jnp.float32) for k, v in state.items()},
        scalars=plane_scalars(cfg, lay, tree, g), **kw,
    )
    x2 = lay.unpack(x2p, like=tree)
    assert all(
        jax.tree.leaves(
            jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), x1, x2)
        )
    )
    for sk in s1:
        s2 = lay.unpack(s2p[sk], dtype=jnp.float32)
        assert all(
            jax.tree.leaves(
                jax.tree.map(
                    lambda a, b: bool(jnp.array_equal(a, b)), s1[sk], s2
                )
            )
        ), sk


# ---------------------------------------------------------------------------
# Simulator invariants: sparse neighbor maps + engine parity
# ---------------------------------------------------------------------------


@SET
@given(
    st.sampled_from(["ring", "torus", "exp", "full", "one-peer-exp",
                     "one-peer-ring", "random-match"]),
    st.sampled_from([4, 6, 8, 16]),
    st.integers(0, 5),
)
def test_sparse_in_neighbors_match_dense_union(family, n, seed):
    """The engines' sparse per-edge neighbor map (derived from
    ``Topology.edge_classes``) must equal the dense reference union over
    period phases (``repro.sim.runner._in_neighbors`` scans every W(t) row)
    — for every family, including the time-varying ones, at random sizes."""
    from repro.core.topology import TopologySpec
    from repro.sim.runner import _in_neighbors

    if family == "torus" and int(np.sqrt(n)) ** 2 != n:
        n = 16
    if family == "one-peer-exp":
        n = 1 << (n - 1).bit_length()  # power-of-two hypercube matchings
    if family in ("one-peer-ring", "random-match") and n % 2:
        n += 1
    spec = TopologySpec(family=family, seed=seed) if family == "random-match" \
        else TopologySpec(family=family)
    topo = spec.build(n)
    dense = _in_neighbors(topo)
    sparse = topo.in_neighbors()
    assert len(sparse) == topo.n
    for i in range(topo.n):
        assert set(sparse[i]) == dense[i], (family, n, i)
        assert list(sparse[i]) == sorted(sparse[i])
    # CSR form agrees with the tuple form
    indptr, indices = topo.in_neighbor_csr()
    for i in range(topo.n):
        assert list(indices[indptr[i]:indptr[i + 1]]) == list(sparse[i])


@settings(max_examples=10, deadline=None)
@given(
    st.integers(0, 10_000),
    st.sampled_from([1, 2, 4, 8]),
    st.lists(st.sampled_from([1.0, 1.5, 2.0, 3.0]), min_size=8, max_size=8),
    st.booleans(),
    st.booleans(),
)
def test_event_engines_bit_exact_on_random_scenarios(
    seed, max_staleness, speeds, with_failstop, with_linkdeg
):
    """Vectorized vs per-node engine on *randomized* scenarios: arbitrary
    constant speed mixes (full ties, partial ties, no ties), random SSP
    bounds, optional fail-stop (reroute) and link degradation.  Full
    SimResult bit-equality — the generative version of the pinned registry
    parity test."""
    from repro.core import OptimizerConfig, make_optimizer
    from repro.sim import FailStop, LinkDegrade, Scenario, SimSpec, simulate
    from repro.sim.clock import ConstantDuration

    events = ()
    if with_failstop:
        events += (FailStop(at_step=4, nodes=(3,)),)
    if with_linkdeg:
        events += (LinkDegrade(at_step=3, edges=((0, 1), (5, 6)), delay=1.75),)
    sc = Scenario(
        name="rand", max_staleness=max_staleness, events=events,
        speeds=lambda n, _sp=tuple(speeds): [ConstantDuration(s) for s in _sp],
    )
    opt = make_optimizer(OptimizerConfig(algorithm="dmsgd", momentum=0.8))
    x0 = jnp.zeros((8, 5), jnp.float32)
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((8, 5, 5)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((8, 5)), jnp.float32)

    def grad_fn(x, _s):
        return jnp.einsum("nij,nj->ni", A, x) + b

    kw = dict(topology="ring", n=8, n_steps=12, lr=1e-2, scenario=sc,
              seed=seed, record_dt=2.5)
    r1 = simulate(opt, SimSpec(engine="pernode", **kw), x0, grad_fn)
    r2 = simulate(opt, SimSpec(engine="vectorized", **kw), x0, grad_fn)
    assert bool(jnp.all(r1.params == r2.params))
    assert all(
        bool(jnp.all(a == b2)) for a, b2 in
        zip(jax.tree.leaves(r1.opt_state), jax.tree.leaves(r2.opt_state))
    )
    assert (r1.steps == r2.steps).all()
    assert (r1.stall_time == r2.stall_time).all()
    assert r1.sim_time == r2.sim_time
    assert r1.trace == r2.trace
    assert r1.events_log == r2.events_log


# ---------------------------------------------------------------------------
# Row-sparse gossip: random row-set sequences x delay x topology
# ---------------------------------------------------------------------------


@SET
@given(
    st.sampled_from(["ring", "exp", "one-peer-exp"]),
    st.integers(0, 2),
    st.integers(0, 2**31 - 1),
    st.booleans(),
)
def test_sparse_channel_random_rowsets_match_dense(name, delay, seed, all_dirty):
    """Generative form of the sparse-channel contract: random per-node
    touched-row sequences, with local updates supported exactly on the
    touched rows (consensus init, no decay — the regime exact tracking is
    sound in).  When every row is dirty, exact AND delta sparse outputs are
    bit-equal to the dense channel's every step, at every delay (delta:
    delay 0 only, by its own precondition).  Under random partial row sets,
    the exact trajectory matches dense to accumulation tolerance and rows
    no node ever touched keep their exact initial bits."""
    from repro.sparse import SparseStackedChannel

    n, R = 8, 6
    topo = build_topology(name, n)
    dense = DelayedStackedChannel(topo, delay)
    sparse = SparseStackedChannel(topo, delay)
    delta = SparseStackedChannel(topo, mode="delta") if delay == 0 else None
    rng = np.random.default_rng(seed)
    x0 = jnp.asarray(
        np.broadcast_to(rng.standard_normal((1, R)), (n, R)), jnp.float32
    )
    xd = xs = xdl = x0
    sd, ss = dense.init(x0), sparse.init(x0)
    sdl = delta.init(x0) if delta is not None else None
    never = np.ones(R, bool)
    for t in range(6):
        m = np.ones((n, R), bool) if all_dirty else rng.random((n, R)) < 0.3
        never &= ~m.any(axis=0)
        u = jnp.asarray(
            np.where(m, rng.standard_normal((n, R)), 0.0), jnp.float32
        )
        xd, xs = xd + u, xs + u
        sd, xd = dense.apply(sd, xd, jnp.int32(t))
        ss = sparse.mark(ss, jnp.asarray(m))
        ss, xs = sparse.apply(ss, xs, jnp.int32(t))
        if delta is not None:
            xdl = xdl + u
            sdl = delta.mark(sdl, jnp.asarray(m))
            sdl, xdl = delta.apply(sdl, xdl, jnp.int32(t))
        if all_dirty:
            np.testing.assert_array_equal(np.asarray(xd), np.asarray(xs))
            if delta is not None:
                np.testing.assert_array_equal(np.asarray(xd), np.asarray(xdl))
        else:
            np.testing.assert_allclose(
                np.asarray(xd), np.asarray(xs), rtol=1e-5, atol=1e-5
            )
    if not all_dirty and never.any():
        np.testing.assert_array_equal(
            np.asarray(xs)[:, never], np.asarray(x0)[:, never]
        )


# ---------------------------------------------------------------------------
# Resilient mixing: W-stochasticity under arbitrary fault masks
# ---------------------------------------------------------------------------


@SET
@given(
    st.sampled_from(["ring", "torus", "exp", "one-peer-exp", "full"]),
    st.lists(st.booleans(), min_size=8, max_size=8),
    st.integers(0, 7),
)
def test_healed_w_properties_any_fault_mask(name, alive, t):
    """The self-healing invariant (ISSUE 10): for ANY fault mask the
    effective mixing matrix stays row-stochastic with non-negative entries,
    reduces exactly to the static W when no faults fire, freezes dead rows
    to e_i with their columns zeroed, and — W being symmetric — keeps the
    surviving block doubly stochastic (DecentLaM's 1/lr bias correction
    divides by the row sum, so any deficiency would be amplified into the
    update)."""
    from repro.resilience import healed_W

    topo = build_topology(name, 8)
    a = np.asarray(alive, bool)
    t = t % topo.period
    W = np.asarray(topo.W(t), np.float64)
    Wh = healed_W(topo, t, a)
    np.testing.assert_allclose(Wh.sum(axis=1), 1.0, atol=1e-12)
    assert (Wh >= -1e-12).all()
    if a.all():
        np.testing.assert_array_equal(Wh, W)
    for i in np.flatnonzero(~a):
        assert Wh[i, i] == 1.0 and np.count_nonzero(Wh[i]) == 1
        assert np.count_nonzero(np.delete(Wh[:, i], i)) == 0
    # symmetric W => doubly stochastic over the survivor block
    np.testing.assert_allclose(W, W.T, atol=1e-12)
    if a.any():
        np.testing.assert_allclose(Wh.sum(axis=0)[a], 1.0, atol=1e-12)


@SET
@given(
    st.sampled_from(["ring", "exp", "one-peer-exp"]),
    st.lists(st.booleans(), min_size=8, max_size=8),
    st.integers(0, 2**31 - 1),
)
def test_resilient_channel_equals_healed_w(name, alive, seed):
    """One healed round through the live channel is exactly ``healed_W @ x``
    for any trust mask, and with an all-true mask it is bit-exact with the
    unwrapped channel (no float is ever added on the clean path)."""
    from repro.resilience import ResilientChannel, healed_W, with_trust

    topo = build_topology(name, 8)
    a = np.asarray(alive, bool)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((8, 5)), jnp.float32)
    res = ResilientChannel(StackedChannel(topo))
    st_r = with_trust(res.init(x), a)
    _, y = res.apply(st_r, x, jnp.int32(0))
    np.testing.assert_allclose(
        np.asarray(y), healed_W(topo, 0, a) @ np.asarray(x, np.float64),
        atol=1e-5,
    )
    if a.all():
        _, y_plain = StackedChannel(topo).apply({}, x, jnp.int32(0))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_plain))


@SET
@given(st.integers(0, 2**31 - 1), st.integers(1, 6))
def test_chaos_empty_schedule_bitexact_any_trajectory(seed, steps):
    """Property form of the PR gate: a ChaosChannel with an EMPTY schedule
    is bit-exact with the unwrapped channel over arbitrary random
    trajectories (the wrapper must be a pure delegate, not merely close)."""
    from repro.resilience import ChaosChannel, ChaosSchedule

    topo = build_topology("exp", 8)
    plain = StackedChannel(topo)
    chaos = ChaosChannel(StackedChannel(topo), ChaosSchedule())
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((8, 5)), jnp.float32)
    sp, sc = plain.init(x), chaos.init(x)
    for t in range(steps):
        sp, yp = plain.apply(sp, x, jnp.int32(t))
        sc, yc = chaos.apply(sc, x, jnp.int32(t))
        np.testing.assert_array_equal(np.asarray(yp), np.asarray(yc))
        x = yp + jnp.asarray(rng.standard_normal(yp.shape), jnp.float32) * 0.1
