"""Subprocess worker: the fault-tolerant gossip runtime on an 8-device mesh.

Three contracts of the resilience subsystem (ISSUE 10) on real shard_map
meshes, mirroring tests/scripts/distributed_delayed.py's harness:

A. **Fail-stop cross-validation**: a live 8-node mesh that loses nodes
   (0, 1) a third of the way in — detected out-of-band
   (``HealthMonitor.report_dead``, the wire image of the simulator's
   oracle event controller), consensus-collapsed over the survivors, and
   rebuilt at the ``plan_recovery`` size — tracks the simulator's
   ``failstop_quarter`` trajectory (allclose) for DSGD, DmSGD and
   staleness-aware DecentLaM.  Phase 1 runs through a ``ChaosChannel``
   whose silence window only opens at the failure step, pinning that an
   inactive schedule is transparent *under shard_map* too.

B. **Transparent wrappers**: ``ResilientChannel(ChaosChannel(ch, empty))``
   with an all-trusted mask is **bit-exact** with the unwrapped delay-0
   ppermute channel for all 11 algorithms (no float is ever added on the
   clean path — every edit is a where-select).

C. **Chaos soak**: decentlam-sa under seeded drop + bit-corrupt + peer
   churn (silence then rejoin) with the full stack live — gap-driven
   health tracking off ``fleet_sender_gaps``, trust-masked self-healing
   mixing, NaN/Inf payload quarantine, and a checkpoint-free rejoin that
   clones a donor's consensus-gated ``WeightPublisher`` snapshot — stays
   finite, quarantines the corruption, and converges with bounded bias.
"""

import dataclasses

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
from repro.core.gossip import fleet_node_gaps
from repro.core.planes import PlaneLayout
from repro.launch.elastic import plan_recovery
from repro.launch.mesh import make_mesh
from repro.resilience import (
    ChaosChannel,
    ChaosSchedule,
    Drop,
    HealthConfig,
    HealthMonitor,
    NaNInject,
    PeerSilence,
    ResilientChannel,
    fleet_sender_gaps,
    rejoin_node,
    with_trust,
)
from repro.serve import WeightPublisher
from repro.sim import SimSpec, simulate

N, D, M = 8, 6, 10
LR = 1e-2
TOPO = "ring"

prob = make_linear_regression(n=N, m=M, d=D, noise=0.01, seed=3, heterogeneity=1.0)


def restrict(indices):
    sel = np.asarray(indices)
    sub = dataclasses.replace(prob, A=prob.A[sel], b=prob.b[sel])
    return lambda x, _s: sub.grad(x)


def grad_fn(x, _s):
    return prob.grad(x)


# --- shard_map harness (mirrors train/step.py's state layout) --------------


def make_runner(n, data_rows):
    """A run_distributed over the first ``n`` devices and the given
    global data rows; returns (runner, mesh)."""
    mesh = make_mesh((n,), ("data",), devices=jax.devices()[:n])
    mean = make_psum_mean(("data",), n)
    rows = np.asarray(list(data_rows))
    A = prob.A[rows]
    b = prob.b[rows]

    def run(opt, gossip, chstate0, n_steps, x0=None, s0=None, k0=0,
            on_step=None):
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
            return jax.tree.map(
                lambda a: P("data", *([None] * (a.ndim - 1))), tree
            )

        if x0 is None:
            x0 = jnp.zeros((n, D), jnp.float32)
        if s0 is None:
            s0 = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
                opt.init(jnp.zeros((D,), jnp.float32)),
            )
        ch0 = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), chstate0
        )
        state = {"x": x0, "opt": s0, "ch": ch0, "k": jnp.int32(k0)}
        sspecs = {"x": specs(x0), "opt": specs(s0), "ch": specs(ch0), "k": P()}
        dspecs = (P("data", None, None), P("data", None))

        step_sm = jax.jit(jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(sspecs, *dspecs),
            out_specs=sspecs,
            axis_names={"data"},
        ))
        Ad = jax.device_put(A, NamedSharding(mesh, dspecs[0]))
        bd = jax.device_put(b, NamedSharding(mesh, dspecs[1]))
        for _ in range(n_steps):
            state = step_sm(state, Ad, bd)
            if on_step is not None:
                state = on_step(state) or state
        return state

    return run


run8 = make_runner(N, range(N))
topo = build_topology(TOPO, N)


# --- A: live-mesh fail-stop tracks the sim's failstop_quarter --------------
# failstop_quarter at n=8: FailStop(at_step=3, nodes=(0, 1)).  In the event
# engine the failure fires the moment the fastest node completes step 3, so
# the survivors collapse at their step-2 iterates; plan_recovery("ring", 8,
# [0, 1]) is over the reroute budget -> rescale, and ring builds at any
# size, so ALL six survivors are kept (the old power-of-two floor threw two
# of them away).  The mesh mirror: 2 synchronous rounds at 8 nodes (through
# a chaos wrapper whose silence window never opens), consensus-collapse
# rows 2..7, rebuild at plan.n_nodes=6 on 6 devices with the survivors'
# data shards, and run the remaining rounds from step 2.

STEPS_A = 9
S0 = max(1, STEPS_A // 3)
mon_a = HealthMonitor(N)
mon_a.report_dead([0, 1])  # oracle liveness, like the sim's event controller
plan = plan_recovery(TOPO, N, mon_a.dead())
assert plan.mode == "rescale" and plan.n_nodes == 6, plan
run6 = make_runner(plan.n_nodes, range(2, N))

for algorithm in ("dsgd", "dmsgd", "decentlam-sa"):
    opt = make_optimizer(OptimizerConfig(algorithm=algorithm, momentum=0.8))
    inner = DelayedPpermuteChannel(
        topo, ("data",), 0, calls_per_step=opt.gossips_per_step
    )
    # the silence window opens exactly at the failure step — phase 1 stops
    # one round short, so the schedule must be bitwise inert here
    chaos = ChaosChannel(
        inner,
        ChaosSchedule(faults=(PeerSilence(nodes=(0, 1), start=S0 - 1),)),
    )
    st1 = run8(
        opt, chaos, chaos.init(jnp.zeros((D,), jnp.float32)), S0 - 1
    )
    survivors = np.arange(2, N)
    xbar = jnp.mean(jnp.asarray(np.asarray(st1["x"])[survivors]), axis=0)
    x2 = jnp.broadcast_to(xbar[None], (plan.n_nodes, D))
    s2 = jax.tree.map(
        lambda a: jnp.broadcast_to(
            jnp.mean(jnp.asarray(np.asarray(a)[survivors]), axis=0)[None],
            (plan.n_nodes,) + a.shape[1:],
        ),
        st1["opt"],
    )
    ch6 = DelayedPpermuteChannel(
        plan.topology, ("data",), 0, calls_per_step=opt.gossips_per_step
    )
    st2 = run6(
        opt, ch6, ch6.init(jnp.zeros((D,), jnp.float32)),
        STEPS_A - (S0 - 1), x0=x2, s0=s2, k0=S0 - 1,
    )
    got = np.asarray(st2["x"])

    res = simulate(
        opt,
        SimSpec(topology=TOPO, n=N, lr=LR, n_steps=STEPS_A,
                scenario="failstop_quarter", restrict=restrict),
        jnp.zeros((N, D), jnp.float32),
        grad_fn,
    )
    assert res.recovery_mode == "rescale" and res.n_nodes == plan.n_nodes, (
        res.recovery_mode, res.n_nodes)
    ref = np.asarray(res.params)
    err = float(np.max(np.abs(got - ref)))
    assert np.allclose(got, ref, atol=1e-4), (algorithm, err)
    print(f"A {algorithm}: OK maxerr={err:.2e}")

# --- B: empty-schedule chaos + all-trusted resilient are bit-exact ---------

STEPS_B = 3
for algorithm in ALGORITHMS:
    opt = make_optimizer(OptimizerConfig(algorithm=algorithm, momentum=0.8))

    def ch0():
        return DelayedPpermuteChannel(
            topo, ("data",), 0, calls_per_step=opt.gossips_per_step
        )

    plain = ch0()
    wrapped = ResilientChannel(ChaosChannel(ch0(), ChaosSchedule()))
    ref = run8(
        opt, plain, plain.init(jnp.zeros((D,), jnp.float32)), STEPS_B
    )
    got = run8(
        opt, wrapped, wrapped.init(jnp.zeros((D,), jnp.float32)), STEPS_B
    )
    assert np.array_equal(np.asarray(got["x"]), np.asarray(ref["x"])), (
        algorithm, float(np.max(np.abs(np.asarray(got["x"]) - np.asarray(ref["x"])))))
    for a, b in zip(jax.tree.leaves(ref["opt"]), jax.tree.leaves(got["opt"])):
        assert np.array_equal(np.asarray(a), np.asarray(b)), algorithm
    assert int(np.asarray(got["ch"]["res"]["quarantined"]).sum()) == 0
    print(f"B {algorithm}: OK (bit-exact)")

# --- C: chaos soak with the full stack live --------------------------------

STEPS_C = 26
SILENCE = (6, 14)  # node 5 fail-stops at 6, rejoins at 14
soak_sched = ChaosSchedule(
    faults=(
        Drop(prob=0.1),
        # NaNInject, not BitCorrupt: a bit-30 flip on values < 2 yields a
        # HUGE-BUT-FINITE float (~1e38) that sails through the isfinite
        # quarantine and overflows the local momentum update — that fault
        # class is the train-step finite-guard's job (it zeroes the grad
        # before the gossip publish), not the channel's
        NaNInject(nodes=(3,), start=4, stop=12, prob=0.5, frac=0.5),
        PeerSilence(nodes=(5,), start=SILENCE[0], stop=SILENCE[1]),
    ),
    seed=0,
)
opt = make_optimizer(OptimizerConfig(algorithm="decentlam-sa", momentum=0.8))
soak_ch = ResilientChannel(
    ChaosChannel(
        DelayedPpermuteChannel(
            topo, ("data",), 0, calls_per_step=opt.gossips_per_step
        ),
        soak_sched,
    ),
    suspect_gap=3,
)
mon = HealthMonitor(
    N, HealthConfig(suspect_after=2, dead_after=2, max_retries=0)
)
pub = WeightPublisher(
    PlaneLayout.build({"w": np.zeros(D, np.float32)}), gap_threshold=1
)
applied = mon.trust.copy()
was_dead = [False]


def drive(state):
    k = int(state["k"])  # steps completed so far
    global applied
    trust = mon.observe(fleet_sender_gaps(soak_ch, state["ch"]))
    if 5 in mon.dead():
        was_dead[0] = True
    if k == SILENCE[1]:
        # checkpoint-free rejoin: clone donor 2's consensus-gated snapshot,
        # row-surgery params + momentum, resurrect in monitor + trust mask
        gaps = fleet_node_gaps(soak_ch, state["ch"])
        assert pub.offer(
            {"w": np.asarray(state["x"])[2]}, version=k, gap=int(gaps[2])
        ), ("donor gate held", gaps)
        snap = pub.current.materialize()
        state = rejoin_node(state, 5, snap.params["w"], params_key="x",
                            reset=("opt",))
        mon.report_alive([5])
        trust = mon.trust
    if not np.array_equal(trust, applied):
        state = dict(state)
        state["ch"] = with_trust(state["ch"], trust)
        applied = trust.copy()
    return state


final = run8(
    opt, soak_ch, soak_ch.init(jnp.zeros((D,), jnp.float32)), STEPS_C,
    on_step=drive,
)

xs = np.asarray(final["x"])
assert np.isfinite(xs).all(), "soak produced non-finite params"
for leaf in jax.tree.leaves(final["opt"]):
    assert np.isfinite(np.asarray(leaf)).all(), "quarantine leaked into momentum"
quar = int(np.asarray(final["ch"]["res"]["quarantined"]).sum())
assert quar > 0, "bit-corrupt faults were never quarantined"
assert was_dead[0], "silent peer was never declared dead"
assert mon.states()[5] == "alive", mon.states()
events = {
    k: int(np.asarray(v)[0].sum())  # (N, n) replicated per-node counters
    for k, v in final["ch"]["in"]["x"]["events"].items()
}
assert events["silence"] > 0 and events["nan"] > 0 and events["drop"] > 0

bias0 = float(np.linalg.norm(-np.asarray(prob.x_star)))  # x starts at 0
bias = float(np.linalg.norm(xs.mean(axis=0) - np.asarray(prob.x_star)))
assert bias < 0.5 * bias0, (bias, bias0)
spread = float(np.abs(xs - xs.mean(axis=0)).max())
print(f"C soak: OK bias={bias:.3f} (start {bias0:.3f}) quarantined={quar} "
      f"spread={spread:.2e} events={events}")

print(f"resilience-distributed: OK ({3 + len(ALGORITHMS) + 1} cases)")
