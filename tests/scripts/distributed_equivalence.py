"""Subprocess worker: distributed shard_map path == stacked reference.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8 (set by the
pytest wrapper).  Covers ppermute gossip, allgather-baseline gossip,
compression, and a fault-excluded topology.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import tiny_lm
from repro.core import (
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

MODE = sys.argv[1] if len(sys.argv) > 1 else "baseline"

if MODE.startswith("planes"):
    # Flat-plane fast path vs the per-leaf path on the same 8-device mesh:
    # identical trajectories (leaf-exact) AND the collapsed collective
    # count — the plane step must ppermute one buffer per dtype bucket per
    # edge class where the per-leaf step ppermutes every pytree leaf.
    # "planes" runs plain decentlam on 8 nodes x tp=1; "planes-delayed"
    # runs decentlam-sa over a delay-2 DelayedPpermuteChannel (ring buffers
    # in plane layout); "planes-tp" reruns BOTH cases on a 4-node x 2-way-TP
    # mesh with the sharded layout — per-rank local buckets, same collapsed
    # ppermute count as tp=1 (the model axis adds no gossip collectives).
    from repro.launch.costmodel import count_primitive
    from repro.train.train_state import init_train_state as _init_state
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
    params_tmpl = jax.eval_shape(
        lambda k: T.init_params(k, cfg, tp=TP), jax.random.key(0)
    )
    n_leaves = len(jax.tree.leaves(params_tmpl))
    layout = model_plane_layout(cfg, TP)
    n_buckets = len(layout.segments)
    classes = len(build_topology("ring", N).edge_classes(0))

    for algo, delay in cases:
        common = dict(
            algorithm=algo, topology="ring", momentum=0.9, gossip_delay=delay,
            schedule=ScheduleConfig(kind="constant", peak_lr=1e-2),
            runtime=T.RuntimeConfig(dtype="float32", remat=False),
        )
        finals, counts = {}, {}
        for flat in (False, True):
            tcfg = TrainConfig(flat_planes=flat, **common)
            opt = make_optimizer(tcfg.opt_config())
            step_fn, _, bspecs, channel = build_train_step(
                cfg, tcfg, mesh, node_axes=("data",)
            )
            state = _init_state(
                jax.random.key(0), cfg, opt, N, TP, mesh=mesh,
                node_axes=("data",),
                channel=channel, plane_layout=layout if flat else None,
            )
            bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                                  is_leaf=lambda x: isinstance(x, P))
            b0 = jax.tree.map(lambda x, sh: jax.device_put(jnp.asarray(x), sh),
                              data.batch(0), bshard)
            counts[flat] = count_primitive(
                jax.make_jaxpr(step_fn)(state, b0), "ppermute"
            )
            for k in range(3):
                b = jax.tree.map(
                    lambda x, sh: jax.device_put(jnp.asarray(x), sh),
                    data.batch(k), bshard,
                )
                state, metrics = step_fn(state, b)
            assert np.isfinite(float(metrics["loss"]))
            finals[flat] = jax.device_get(state["params"])

        maxerr = max(
            float(np.max(np.abs(
                np.asarray(a, np.float32) - np.asarray(b, np.float32)
            )))
            for a, b in zip(jax.tree.leaves(finals[False]),
                            jax.tree.leaves(finals[True]))
        )
        assert maxerr == 0.0, (
            f"{MODE}/{algo}: plane vs per-leaf trajectories differ: {maxerr}"
        )
        # the per-device program carries one ppermute per leaf (per-leaf
        # path) / per bucket (plane path) per edge class, REGARDLESS of tp:
        # the tp > 1 counts must equal the tp == 1 collapse exactly
        assert counts[False] == classes * n_leaves, (counts, classes, n_leaves)
        assert counts[True] == classes * n_buckets, (counts, classes, n_buckets)
        print(f"{MODE}/{algo}: OK bit-exact; ppermutes/step {counts[False]} "
              f"-> {counts[True]} ({n_leaves} leaves -> {n_buckets} "
              f"bucket(s) x {classes} edge classes, tp={TP})")
    print(f"{MODE}: OK bit-exact")
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
        algorithm="decentlam", topology="ring", momentum=0.9, flat_planes=True,
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
tol = 2e-5
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
    # exercises the fused-update code path in step.py (payload -> gossip ->
    # fused tail).  impl="ref" is bit-identical math to the Pallas kernel
    # (validated elementwise in tests/test_kernels.py); interpret-mode Pallas
    # can't trace inside a check_vma shard_map on CPU (its Python block
    # slicing mixes variances) — on TPU the real kernel lowers natively.
    kwargs["fused_update"] = True
    kwargs["fused_impl"] = "ref"

tcfg = TrainConfig(**kwargs)
opt = make_optimizer(tcfg.opt_config())
step_fn, _, bspecs, channel = build_train_step(cfg, tcfg, mesh, node_axes=("data",))
state = init_train_state(jax.random.key(0), cfg, opt, N, TP, mesh=mesh,
                         node_axes=("data",), channel=channel)
data = SyntheticLM(SyntheticLMConfig(vocab_size=256, seq_len=S, per_node_batch=2,
                                     n_nodes=N, heterogeneity=0.5))
bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs,
                      is_leaf=lambda x: isinstance(x, P))
for k in range(3):
    b = jax.tree.map(lambda x, sh: jax.device_put(jnp.asarray(x), sh),
                     data.batch(k), bshard)
    state, metrics = step_fn(state, b)
assert np.isfinite(float(metrics["loss"]))

# stacked reference with plain (uncompressed, dense-W) channel
rt = tcfg.runtime
tp1 = TPContext(size=1)
topo = build_topology(kwargs["topology"], N)
g_ref, m_ref = StackedChannel(topo), make_stacked_mean(N)
params0 = T.init_params(jax.random.key(0), cfg, tp=TP)
ref_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (N,) + x.shape), params0)
ref_o = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (N,) + x.shape),
                     opt.init(params0))


def per_node_grads(sp, batch):
    def one(p, bt, bg):
        def lf(pp):
            return T.forward_loss(pp, {"tokens": bt, "targets": bg}, cfg, tp1, rt)
        (l, mm), g = jax.value_and_grad(lf, has_aux=True)(p)
        return g, l

    bt = batch["tokens"].reshape(N, -1, S)
    bg = batch["targets"].reshape(N, -1, S)
    return jax.vmap(one)(sp, bt, bg)


@jax.jit
def ref_step(sp, so, batch, k):
    g, l = per_node_grads(sp, batch)
    p2, o2, _ = opt.step(sp, g, so, lr=jnp.float32(1e-2), step_idx=k,
                         gossip=g_ref, mean=m_ref)
    return p2, o2


for k in range(3):
    b = {kk: jnp.asarray(v) for kk, v in data.batch(k).items()}
    ref_p, ref_o = ref_step(ref_p, ref_o, b, jnp.int32(k))

errs = jax.tree.leaves(jax.tree.map(
    lambda a, b_: float(np.max(np.abs(np.asarray(a) - np.asarray(b_)))),
    state["params"], ref_p))
maxerr = max(errs)
assert maxerr < tol, f"{MODE}: {maxerr}"
if MODE == "topk":
    ef = [np.abs(np.asarray(x)).sum()
          for x in jax.tree.leaves(state["channel"]["comp"])]
    assert sum(ef) > 0.0, "error-feedback residuals never populated"
tele = state["channel"]["t"]
assert int(tele["rounds"][0]) == 3 * opt.gossips_per_step, tele
assert float(tele["bytes"][0]) > 0.0
print(f"{MODE}: OK maxerr={maxerr:.2e}")
