"""Subprocess worker: distributed shard_map path == stacked reference.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
pytest wrapper).  Covers ppermute gossip, allgather-baseline gossip,
compression, a fault-excluded topology, the Pallas stage's trace, the
flat-plane collective counts and row-sparse gossip.
"""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import tiny_lm
from repro.core import (
    DelayedStackedChannel,
    StackedChannel,
    build_topology,
    make_optimizer,
    make_stacked_mean,
)
from repro.core.schedules import ScheduleConfig
from repro.data.synthetic import SyntheticLM, SyntheticLMConfig
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.layers import TPContext
from repro.train.step import TrainConfig, build_train_step
from repro.train.train_state import init_train_state


def put(batch, bshard):
    return jax.tree.map(lambda x, sh: jax.device_put(jnp.asarray(x), sh),
                        batch, bshard)


def run_step(cfg, tcfg, mesh, N, TP, data, steps=3):
    """``steps`` steps of the shard_map train step; returns the final state
    and the step's ppermute count (from its jaxpr)."""
    from repro.launch.costmodel import count_primitive

    opt = make_optimizer(tcfg.opt_config())
    step_fn, _, bspecs, channel = build_train_step(
        cfg, tcfg, mesh, node_axes=("data",)
    )
    state = init_train_state(jax.random.key(0), cfg, opt, N, TP, mesh=mesh,
                             node_axes=("data",), channel=channel)
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                          is_leaf=lambda x: isinstance(x, P))
    n_ppermute = count_primitive(
        jax.make_jaxpr(step_fn)(state, put(data.batch(0), bshard)), "ppermute"
    )
    for k in range(steps):
        state, metrics = step_fn(state, put(data.batch(k), bshard))
    assert np.isfinite(float(metrics["loss"]))
    return state, n_ppermute


def reference_params(cfg, tcfg, N, TP, S, data, steps=3):
    """The stacked oracle: per-node grads (vmapped, unsharded) and the
    per-leaf float32 ``opt.step`` over a plain dense-W channel (delayed
    when the step's channel is)."""
    rt = tcfg.runtime
    tp1 = TPContext(size=1)
    opt = make_optimizer(tcfg.opt_config())
    topo = build_topology(tcfg.topology, N)
    if tcfg.gossip_delay:
        g_ref = DelayedStackedChannel(topo, tcfg.gossip_delay,
                                      calls_per_step=opt.gossips_per_step)
    else:
        g_ref = StackedChannel(topo)
    m_ref = make_stacked_mean(N)
    params0 = T.init_params(jax.random.key(0), cfg, tp=TP)
    ref_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (N,) + x.shape),
                         params0)
    ref_o = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (N,) + x.shape),
                         opt.init(params0))
    ref_c = g_ref.init(ref_p)
    lr = tcfg.schedule.peak_lr

    def per_node_grads(sp, batch):
        def one(p, bt, bg):
            def lf(pp):
                return T.forward_loss(pp, {"tokens": bt, "targets": bg}, cfg,
                                      tp1, rt)
            (l, mm), g = jax.value_and_grad(lf, has_aux=True)(p)
            return g, l

        bt = batch["tokens"].reshape(N, -1, S)
        bg = batch["targets"].reshape(N, -1, S)
        return jax.vmap(one)(sp, bt, bg)

    @jax.jit
    def ref_step(sp, so, sc, batch, k):
        g, l = per_node_grads(sp, batch)
        return opt.step(sp, g, so, lr=jnp.float32(lr), step_idx=k,
                        gossip=g_ref, mean=m_ref, comp_state=sc)

    for k in range(steps):
        b = {kk: jnp.asarray(v) for kk, v in data.batch(k).items()}
        ref_p, ref_o, ref_c = ref_step(ref_p, ref_o, ref_c, b, jnp.int32(k))
    return ref_p


def max_err(a, b) -> float:
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(np.max(np.abs(
            np.asarray(x, np.float32) - np.asarray(y, np.float32)))),
        a, b)))


MODE = sys.argv[1] if len(sys.argv) > 1 else "baseline"
TOL = 2e-5

if MODE.startswith("planes"):
    # The flat-plane step vs the stacked per-leaf oracle on the same mesh,
    # AND the collapsed collective count — the plane step ppermutes one
    # buffer per dtype bucket per edge class.  "planes" runs plain
    # decentlam on 8 nodes x tp=1; "planes-delayed" runs decentlam-sa over
    # a delay-2 DelayedPpermuteChannel (ring buffers in plane layout);
    # "planes-tp" reruns BOTH cases on a 4-node x 2-way-TP mesh with the
    # sharded layout — per-rank local buckets, same collapsed ppermute
    # count as tp=1 (the model axis adds no gossip collectives).
    from repro.train.train_state import model_plane_layout

    S = 32
    N, TP = (4, 2) if MODE == "planes-tp" else (8, 1)
    if MODE == "planes-tp":
        cases = [("decentlam", 0), ("decentlam-sa", 2)]
    elif MODE == "planes-delayed":
        cases = [("decentlam-sa", 2)]
    else:
        cases = [("decentlam", 0)]
    mesh = make_mesh((N, TP), ("data", "model"))
    cfg = tiny_lm(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256
    )
    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=256, seq_len=S, per_node_batch=2, n_nodes=N,
        heterogeneity=0.5,
    ))
    n_buckets = len(model_plane_layout(cfg, TP).segments)
    classes = len(build_topology("ring", N).edge_classes(0))

    for algo, delay in cases:
        tcfg = TrainConfig(
            algorithm=algo, topology="ring", momentum=0.9, gossip_delay=delay,
            schedule=ScheduleConfig(kind="constant", peak_lr=1e-2),
            runtime=T.RuntimeConfig(dtype="float32", remat=False),
        )
        state, n_ppermute = run_step(cfg, tcfg, mesh, N, TP, data)
        err = max_err(state["params"],
                      reference_params(cfg, tcfg, N, TP, S, data))
        assert err < TOL, f"{MODE}/{algo}: plane step vs oracle: {err}"
        # the per-device program carries one ppermute per bucket per edge
        # class, REGARDLESS of tp: the tp > 1 count equals the tp == 1
        # collapse exactly
        assert n_ppermute == classes * n_buckets, (n_ppermute, classes, n_buckets)
        print(f"{MODE}/{algo}: OK maxerr={err:.2e}; ppermutes/step "
              f"{n_ppermute} ({n_buckets} bucket(s) x {classes} edge "
              f"classes, tp={TP})")
    print(f"{MODE}: OK")
    sys.exit(0)

if MODE == "sparse":
    # Row-sparse gossip on the production train step (granite-moe SMOKE,
    # flat planes, 8-node mesh).  Three runs: dense channel; sparse with
    # crossover ~0 (every round hits the dense fallback — must be BIT-EXACT
    # with dense end-to-end); sparse at the default crossover (embedding +
    # expert rows ride the RowTracker — must ship measurably fewer bytes
    # while the dense-tracked planes keep training).
    from repro.configs import get_config
    from repro.train.train_state import model_plane_layout

    N, TP, S = 8, 1, 32
    mesh = make_mesh((N, TP), ("data", "model"))
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    layout = model_plane_layout(cfg, TP)
    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=S, per_node_batch=2, n_nodes=N,
        heterogeneity=0.5,
    ))
    common = dict(
        algorithm="decentlam", topology="ring", momentum=0.9,
        schedule=ScheduleConfig(kind="constant", peak_lr=1e-2),
        runtime=T.RuntimeConfig(dtype="float32", remat=False),
    )
    finals, teles = {}, {}
    for variant in ("dense", "sparse-all", "sparse"):
        kw = dict(common)
        if variant != "dense":
            kw["sparse_gossip"] = True
            kw["sparse_crossover"] = 1e-9 if variant == "sparse-all" else 0.9
        tcfg = TrainConfig(**kw)
        opt = make_optimizer(tcfg.opt_config())
        step_fn, _, bspecs, channel = build_train_step(
            cfg, tcfg, mesh, node_axes=("data",)
        )
        state = init_train_state(
            jax.random.key(0), cfg, opt, N, TP, mesh=mesh, node_axes=("data",),
            channel=channel, plane_layout=layout,
        )
        bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                              is_leaf=lambda x: isinstance(x, P))
        for k in range(4):
            b = jax.tree.map(lambda x, sh: jax.device_put(jnp.asarray(x), sh),
                             data.batch(k), bshard)
            state, metrics = step_fn(state, b)
        assert np.isfinite(float(metrics["loss"])), variant
        finals[variant] = jax.device_get(state["params"])
        ch = jax.device_get(state["channel"])
        teles[variant] = {"bytes": float(ch["t"]["bytes"][0])}
        if "rows" in ch:
            vol = ch["rows"]["vol"]
            teles[variant]["vol"] = (
                float(np.mean(vol["sparse"])), float(np.mean(vol["dense"])),
            )
    err = max(
        float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree.leaves(finals["dense"]),
                        jax.tree.leaves(finals["sparse-all"]))
    )
    assert err == 0.0, f"sparse-all (forced dense fallback) vs dense: {err}"
    bd, bs = teles["dense"]["bytes"], teles["sparse"]["bytes"]
    assert bs < bd, (bs, bd)
    vs, vdense = teles["sparse"]["vol"]
    assert vs < vdense, teles["sparse"]
    print(f"sparse: OK bit-exact under forced fallback; measured bytes "
          f"{bs:.0f} vs dense {bd:.0f} (ratio {bs / bd:.3f})")
    sys.exit(0)

cfg = tiny_lm(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256)
mesh = make_mesh((4, 2), ("data", "model"))
N, TP, S = 4, 2, 32

kwargs = dict(
    algorithm="decentlam", topology="ring", momentum=0.9,
    schedule=ScheduleConfig(kind="constant", peak_lr=1e-2),
    runtime=T.RuntimeConfig(dtype="float32", remat=False),
)
tol = TOL
if MODE == "allgather":
    kwargs["gossip_impl"] = "allgather"
elif MODE == "compressed":
    kwargs["compression"] = "bf16"
    tol = 5e-2  # bf16 messages change the trajectory slightly
elif MODE == "one-peer":
    kwargs["topology"] = "one-peer-exp"
elif MODE == "topk":
    # top-k sparsified gossip with error feedback: the trajectory deviates
    # from the dense reference by design; assert training stays finite and
    # the error-feedback state is being populated.
    kwargs["compression"] = "topk:0.05"
    tol = float("inf")
elif MODE == "fused":
    # the Pallas stage executor: its step traces on the sharded 4x2 layout
    # with one pallas_call per bucket per stage.  Interpret-mode Pallas
    # can't run inside a check_vma shard_map on CPU (its Python block
    # slicing mixes variances), so the trajectory below runs the "ref"
    # stage, whose math the kernel shares (pinned in tests/test_planes.py
    # and tests/test_kernels.py); on TPU the real kernel lowers natively.
    from repro.core.update_spec import stage_plan
    from repro.launch.costmodel import count_primitive
    from repro.train.train_state import model_plane_layout

    tcfg = TrainConfig(fused_impl="pallas_interpret", **kwargs)
    opt = make_optimizer(tcfg.opt_config())
    step_fn, _, bspecs, channel = build_train_step(
        cfg, tcfg, mesh, node_axes=("data",)
    )
    state = init_train_state(jax.random.key(0), cfg, opt, N, TP, mesh=mesh,
                             node_axes=("data",), channel=channel)
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                          is_leaf=lambda x: isinstance(x, P))
    data = SyntheticLM(SyntheticLMConfig(vocab_size=256, seq_len=S,
                                         per_node_batch=2, n_nodes=N,
                                         heterogeneity=0.5))
    n_calls = count_primitive(
        jax.make_jaxpr(step_fn)(state, put(data.batch(0), bshard)),
        "pallas_call",
    )
    want = len(model_plane_layout(cfg, TP).segments) * len(
        stage_plan(tcfg.opt_config()))
    assert n_calls == want, (n_calls, want)
    kwargs["fused_impl"] = "ref"

tcfg = TrainConfig(**kwargs)
data = SyntheticLM(SyntheticLMConfig(vocab_size=256, seq_len=S, per_node_batch=2,
                                     n_nodes=N, heterogeneity=0.5))
state, _ = run_step(cfg, tcfg, mesh, N, TP, data)
# the oracle gossips over a plain uncompressed dense-W channel
ref_p = reference_params(
    cfg, dataclasses.replace(tcfg, compression=None, gossip_impl="ppermute"),
    N, TP, S, data,
)
maxerr = max_err(state["params"], ref_p)
assert maxerr < tol, f"{MODE}: {maxerr}"
if MODE == "topk":
    ef = [np.abs(np.asarray(x)).sum()
          for x in jax.tree.leaves(state["channel"]["comp"])]
    assert sum(ef) > 0.0, "error-feedback residuals never populated"
tele = state["channel"]["t"]
opt = make_optimizer(tcfg.opt_config())
assert int(tele["rounds"][0]) == 3 * opt.gossips_per_step, tele
assert float(tele["bytes"][0]) > 0.0
print(f"{MODE}: OK maxerr={maxerr:.2e}")
