"""Subprocess worker: the delayed ppermute channel on an 8-device CPU mesh.

Two contracts of the GossipChannel redesign's headline capability:

A. ``stale_gossip_k2`` on a real mesh: a shard_map run whose transport is
   :class:`DelayedPpermuteChannel` (payloads held back 2 steps in device
   memory) matches the cluster simulator's SSP trajectory (the delayed
   stacked engine) for DSGD, DmSGD and the staleness-aware DecentLaM
   (allclose) — for ``decentlam-sa`` this also pins that the distributed
   channel's per-node ``node_gaps`` scalar drives the same damping the
   stacked channel's ``(n,)`` gap vector does.

B. Delay-0 channels are **bit-exact** with the pre-redesign ppermute gossip
   for all 11 algorithms (``decentlam-sa`` sees gap 0 from both transports
   — the channel's and the closure's unobservable staleness — so it must
   match too).  The old closure is inlined below as a frozen regression
   oracle (the shipped ``make_ppermute_gossip`` shim was removed after its
   grace period, so this inline copy is the only remaining reference).

C. The serving consensus gate on the real mesh: ``fleet_node_gaps`` read
   off the live channel's distributed state drives a ``WeightPublisher``
   that ships only while the warmup gap is under threshold and never once
   the mesh runs at its configured staleness.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (
    ALGORITHMS,
    DelayedPpermuteChannel,
    OptimizerConfig,
    build_topology,
    make_linear_regression,
    make_optimizer,
    make_psum_mean,
)
from repro.core.compression import get_compressor
from repro.launch.mesh import make_mesh
from repro.sim import SimSpec, simulate

N, D, M = 8, 6, 10
LR = 1e-2
TOPO = "ring"

mesh = make_mesh((N,), ("data",))
prob = make_linear_regression(n=N, m=M, d=D, noise=0.01, seed=3, heterogeneity=1.0)
topo = build_topology(TOPO, N)
mean = make_psum_mean(("data",), N)


# --- frozen pre-redesign ppermute gossip (regression oracle for part B) ----


def legacy_ppermute_gossip(topology, node_axes, *, compression=None,
                           serialize=True):
    import functools

    compressor = get_compressor(compression)
    period = topology.period

    def apply_classes(t, tree, comp_state):
        classes = topology.edge_classes(t)
        self_w = jnp.asarray(topology.self_weight(t), dtype=jnp.float32)
        idx = jax.lax.axis_index(node_axes)
        leaves, treedef = jax.tree.flatten(tree)
        stateless = not jax.tree.leaves(comp_state)
        states = [()] * len(leaves) if stateless else treedef.flatten_up_to(comp_state)
        msgs, new_states = [], []
        for x, st in zip(leaves, states):
            m, st = compressor.encode(x, st)
            msgs.append(m)
            new_states.append(st)
        out = [self_w[idx] * x.astype(jnp.float32) for x in leaves]
        for ci, c in enumerate(classes):
            w = jnp.asarray(c.recv_weight, dtype=jnp.float32)[idx]
            for k, (x, m) in enumerate(zip(leaves, msgs)):
                if serialize and ci > 0:
                    z = out[k].ravel()[:1].sum() * 0
                    m = jax.tree.map(lambda a: a + z.astype(a.dtype), m)
                recv = jax.tree.map(
                    lambda a: jax.lax.ppermute(a, node_axes, c.pairs), m
                )
                out[k] = out[k] + w * compressor.decode(recv, x).astype(jnp.float32)
        out = [o.astype(x.dtype) for o, x in zip(out, leaves)]
        comp_out = comp_state if stateless else treedef.unflatten(new_states)
        return treedef.unflatten(out), comp_out

    def gossip(tree, step, comp_state):
        if period == 1:
            return apply_classes(0, tree, comp_state)
        branches = [functools.partial(apply_classes, t) for t in range(period)]
        return jax.lax.switch(step % period, branches, tree, comp_state)

    return gossip


# --- shard_map harness (mirrors train/step.py's state layout) --------------


def run_distributed(opt, gossip, chstate0, n_steps, on_step=None):
    """Iterate opt over the mesh; returns the gathered (n, d) params."""

    def body(st, Al, bl):
        x = st["x"][0]
        s = jax.tree.map(lambda a: a[0], st["opt"])
        ch = jax.tree.map(lambda a: a[0], st["ch"])
        A0, b0 = Al[0], bl[0]
        g = A0.T @ (A0 @ x - b0)
        x, s, ch = opt.step(
            x, g, s, lr=jnp.float32(LR), step_idx=st["k"], gossip=gossip,
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

    x0 = jnp.zeros((N, D), jnp.float32)
    s0 = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape),
        opt.init(jnp.zeros((D,), jnp.float32)),
    )
    ch0 = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), chstate0
    )
    state = {"x": x0, "opt": s0, "ch": ch0, "k": jnp.int32(0)}
    sspecs = {"x": specs(x0), "opt": specs(s0), "ch": specs(ch0), "k": P()}
    dspecs = (P("data", None, None), P("data", None))

    step_sm = jax.jit(jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(sspecs, *dspecs),
        out_specs=sspecs,
        axis_names={"data"},
    ))
    Ad = jax.device_put(prob.A, NamedSharding(mesh, dspecs[0]))
    bd = jax.device_put(prob.b, NamedSharding(mesh, dspecs[1]))
    for _ in range(n_steps):
        state = step_sm(state, Ad, bd)
        if on_step is not None:
            on_step(state)
    return np.asarray(state["x"])


def grad_fn(x, _s):
    return prob.grad(x)


# --- A: stale_gossip_k2 matches the simulator's SSP trajectory -------------

STEPS_A = 8
for algorithm in ("dsgd", "dmsgd", "decentlam-sa"):
    opt = make_optimizer(OptimizerConfig(algorithm=algorithm, momentum=0.8))
    channel = DelayedPpermuteChannel(
        topo, ("data",), 2, calls_per_step=opt.gossips_per_step
    )
    got = run_distributed(
        opt, channel, channel.init(jnp.zeros((D,), jnp.float32)), STEPS_A
    )
    res = simulate(
        opt,
        SimSpec(topology=TOPO, n=N, lr=LR, n_steps=STEPS_A,
                scenario="stale_gossip_k2"),
        jnp.zeros((N, D), jnp.float32),
        grad_fn,
    )
    ref = np.asarray(res.params)
    err = float(np.max(np.abs(got - ref)))
    assert np.allclose(got, ref, atol=1e-4), (algorithm, err)
    print(f"A {algorithm}: OK maxerr={err:.2e}")

# --- B: delay-0 channel bit-exact with the pre-redesign gossip -------------

STEPS_B = 3
for algorithm in ALGORITHMS:
    opt = make_optimizer(OptimizerConfig(algorithm=algorithm, momentum=0.8))
    channel = DelayedPpermuteChannel(
        topo, ("data",), 0, calls_per_step=opt.gossips_per_step
    )
    got = run_distributed(opt, channel, channel.init(jnp.zeros((D,), jnp.float32)), STEPS_B)
    legacy = legacy_ppermute_gossip(topo, ("data",))
    ref = run_distributed(opt, legacy, {}, STEPS_B)
    assert np.array_equal(got, ref), (
        algorithm, float(np.max(np.abs(got - ref))))
    print(f"B {algorithm}: OK (bit-exact)")

# --- C: the consensus gate on the real-mesh channel ------------------------
# fleet_node_gaps reads the TrainState-layout channel bucket (leaves with a
# leading node axis) of the live DelayedPpermuteChannel and reports the
# warmup-ruled gap min(delay, round-1) on every node; a WeightPublisher
# gating on it ships only the warmup rounds at threshold 1 and holds every
# offer once the mesh runs at its configured staleness.

from repro.core.gossip import fleet_node_gaps
from repro.core.planes import PlaneLayout
from repro.serve import WeightPublisher

STEPS_C, DELAY_C, THR_C = 6, 2, 1
opt = make_optimizer(OptimizerConfig(algorithm="dsgd", momentum=0.8))
channel = DelayedPpermuteChannel(
    topo, ("data",), DELAY_C, calls_per_step=opt.gossips_per_step
)
tree = {"w": jnp.zeros((D,), jnp.float32)}
pub = WeightPublisher(PlaneLayout.build(tree), gap_threshold=THR_C)
rounds = [0]


def gate(state):
    rounds[0] += 1
    gaps = fleet_node_gaps(channel, state["ch"])
    expect = min(DELAY_C, rounds[0] - 1)
    assert gaps.shape == (N,) and (gaps == expect).all(), (rounds[0], gaps)
    pub.offer(tree, version=rounds[0], gap=int(gaps[0]))


run_distributed(
    opt, channel, channel.init(jnp.zeros((D,), jnp.float32)), STEPS_C,
    on_step=gate,
)
warmup = sum(min(DELAY_C, r) <= THR_C for r in range(STEPS_C))
assert pub.published == warmup and pub.rejected == STEPS_C - warmup, pub.stats()
assert pub.current.version == warmup
print(f"C gate: OK (published {pub.published}/{STEPS_C} warmup rounds only)")

print(f"delayed-ppermute: OK ({3 + len(ALGORITHMS)} cases)")
