"""The decentralized train step: fully-manual shard_map over
(pod, data, model).

Per step, on every node (= one (pod, data) mesh index):

1. squeeze this node's replica out of the stacked TrainState;
2. local gradient over the node's batch shard (optionally microbatched with
   fp32 accumulation, per-layer remat, bf16 compute);
3. the selected algorithm's update, with gossip = ppermute edge classes over
   the node axes and mean = psum (PmSGD / SlowMo sync);
4. metrics psum-reduced to replicated scalars.

The update runs on flat planes: params and grads are packed into
dtype-bucketed ``(rows, LANES)`` buffers, every algorithm's elementwise
tail — payload build, momentum accumulate, Nesterov, weight decay, LARS
scaling, recombination — is one stage per bucket, and gossip is one
collective per bucket per edge class.  ``TrainConfig.fused_impl`` picks the
stage: the pure-jnp reference or the Pallas fused-update kernel (one HBM
pass per stage; see ``repro.kernels.fused_update``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.gossip import GossipChannel, build_channel, make_psum_mean
from ..core.optimizers import OptimizerConfig, make_optimizer
from ..core.planes import plane_scalars
from ..core.schedules import ScheduleConfig, build_schedule
from ..core.topology import build_topology
from ..core.update_spec import run_update, update_spec, zero_unless
from ..kernels.fused_update import make_plane_stage
from ..models import transformer as T
from ..models.layers import TPContext
from .train_state import model_plane_layout, stacked_state_specs

Tree = Any

__all__ = ["TrainConfig", "build_train_step", "build_gossip_channel", "batch_specs"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    algorithm: str = "decentlam"
    topology: str = "exp"
    gossip_impl: str = "ppermute"  # ppermute | allgather (naive baseline)
    gossip_delay: int = 0  # hold payloads back k steps (delayed ppermute channel)
    compression: str | None = None
    momentum: float = 0.9
    weight_decay: float = 0.0
    grad_clip: float = 0.0
    # decentlam-sa gap-damping schedule (read off the delayed channel's
    # version gaps; inert for the other algorithms)
    sa_damping: float = 0.5
    sa_floor: float = 0.0
    grad_accum: int = 1
    schedule: ScheduleConfig = ScheduleConfig()
    runtime: T.RuntimeConfig = T.RuntimeConfig()
    # the plane update's stage executor: ref | pallas | pallas_interpret.
    # Optimizer + channel hot state stays in plane form across steps; at
    # tp > 1 the layout is sharded per mesh column, so each TP rank packs
    # only its local shard rows.
    fused_impl: str = "ref"
    # Always True: the step has one update path.  The fields remain only
    # because bench/harness.py passes them (and reads flat_planes); a
    # benchmark change that drops them there removes them here.
    fused_update: bool = True
    flat_planes: bool = True
    gossip_serialize: bool = True  # one recv buffer live at a time (§Perf A-3)
    track_consensus: bool = False
    # row-sparse gossip (repro.sparse): ship only the touched rows of each
    # plane bucket per round.  Requires gossip_impl="ppermute".  "exact" is
    # provably equivalent to dense gossip; "delta" heals rows after delivery
    # (lossy, delay-0 only, benchmarked in BENCH_gossip.json).
    sparse_gossip: bool = False
    sparse_mode: str = "exact"  # exact | delta
    sparse_crossover: float = 0.9  # dirty fraction at which a bucket goes dense
    # fault tolerance (repro.resilience): skip the optimizer update when the
    # local grad norm goes non-finite (the skip count surfaces as the
    # "skipped_nonfinite" metric; launch.train --max-skipped-steps aborts on
    # a budget), inject a seeded fault schedule on the wire, and/or wrap the
    # transport in the self-healing ResilientChannel (trust-masked mixing
    # with W-row renormalization + NaN/Inf payload quarantine)
    finite_guard: bool = True
    chaos: Any = None  # ChaosSchedule | None (frozen/hashable)
    resilient: bool = False
    resilient_gap: int | None = None  # on-device auto-distrust gap bound

    def __post_init__(self):
        if not (self.flat_planes and self.fused_update):
            raise ValueError(
                "the train step always runs the update on flat planes; "
                "fused_impl (ref | pallas | pallas_interpret) picks the stage"
            )

    def opt_config(self) -> OptimizerConfig:
        return OptimizerConfig(
            algorithm=self.algorithm,
            momentum=self.momentum,
            weight_decay=self.weight_decay,
            grad_clip=self.grad_clip,
            sa_damping=self.sa_damping,
            sa_floor=self.sa_floor,
        )


def build_gossip_channel(
    tcfg: "TrainConfig", topology, node_axes, *, gossips_per_step: int | None = None
) -> GossipChannel:
    """The transport for a train config: ppermute/allgather, delayed when
    ``gossip_delay > 0``, telemetry on (per-node rounds/egress-bytes live in
    the TrainState's ``"channel"`` bucket and checkpoint with it)."""
    if tcfg.gossip_impl not in ("ppermute", "allgather"):
        # the stacked channels are the mesh-free oracle layout — inside the
        # per-node shard_map they would mix garbage shapes
        raise ValueError(
            f"gossip_impl={tcfg.gossip_impl!r}; the train step runs inside "
            "shard_map and needs a distributed transport: ppermute | allgather"
        )
    if gossips_per_step is None:
        gossips_per_step = make_optimizer(tcfg.opt_config()).gossips_per_step
    if tcfg.sparse_gossip and (tcfg.chaos is not None or tcfg.resilient):
        # the sparse channels ship per-bucket row segments, not whole-leaf
        # payloads — the resilience wrappers' sender-side masking would
        # corrupt the row->segment addressing
        raise ValueError(
            "chaos/resilient wrappers do not compose with sparse_gossip: "
            "use dense gossip for fault-injection runs"
        )
    if tcfg.sparse_gossip:
        if tcfg.gossip_impl != "ppermute":
            raise ValueError(
                "sparse_gossip requires gossip_impl='ppermute' (the sparse "
                "channels ride the edge-class wire path)"
            )
        if tcfg.gossip_delay > 0 and tcfg.weight_decay != 0.0:
            # delayed exact sparsity skips rows that stay in cross-node
            # consensus; per-step weight decay drifts untouched rows, so the
            # delayed mix would combine different versions of a row the
            # channel never re-ships
            raise ValueError(
                "sparse_gossip with gossip_delay > 0 requires "
                "weight_decay == 0 (untouched rows must be stationary for "
                "delayed exact row-skipping to be lossless)"
            )
        from ..sparse import build_sparse_channel

        return build_sparse_channel(
            "ppermute",
            topology,
            node_axes,
            mode=tcfg.sparse_mode,
            crossover=tcfg.sparse_crossover,
            compression=tcfg.compression,
            delay=tcfg.gossip_delay,
            serialize=tcfg.gossip_serialize,
            calls_per_step=gossips_per_step,
            telemetry=True,
        )
    channel = build_channel(
        tcfg.gossip_impl,
        topology,
        node_axes,
        compression=tcfg.compression,
        delay=tcfg.gossip_delay,
        serialize=tcfg.gossip_serialize,
        calls_per_step=gossips_per_step,
        telemetry=True,
    )
    # resilience wrappers compose outside-in: chaos injects on the wire,
    # the resilient layer heals one level up (so it also heals real faults)
    if tcfg.chaos is not None:
        from ..resilience import ChaosChannel

        channel = ChaosChannel(channel, tcfg.chaos)
    if tcfg.resilient:
        from ..resilience import ResilientChannel

        channel = ResilientChannel(channel, suspect_gap=tcfg.resilient_gap)
    return channel


def batch_specs(cfg: ModelConfig, node_axes) -> Tree:
    s: Tree = {"tokens": P(node_axes, None), "targets": P(node_axes, None)}
    if cfg.family == "vlm":
        s["patch_embeds"] = P(node_axes, None, None)
    if cfg.arch_kind == "encdec":
        s["enc_frames"] = P(node_axes, None, None)
    return s


def _spec_axes(spec: P) -> set:
    """Mesh axes a PartitionSpec shards over."""
    axes = set()
    for entry in spec:
        axes.update(entry if isinstance(entry, tuple) else (entry,))
    return axes - {None}


def _consensus_metric(params: Tree, node_axes, n_nodes: int, model_axis) -> jax.Array:
    """(1/n) sum_i ||x_i - x_bar||^2 across nodes (telemetry; averaged over
    model shards so the scalar is replicated on every device)."""
    total = jnp.float32(0.0)
    for x in jax.tree.leaves(params):
        xf = x.astype(jnp.float32)
        xb = jax.lax.psum(xf, node_axes) / n_nodes
        total = total + jax.lax.psum(jnp.sum((xf - xb) ** 2), node_axes) / n_nodes
    return jax.lax.pmean(total, model_axis)


def build_train_step(
    cfg: ModelConfig,
    tcfg: TrainConfig,
    mesh,
    *,
    node_axes: tuple[str, ...] = ("data",),
    model_axis: str = "model",
):
    """Returns (jitted train_step, state_specs, batch_specs, channel).

    The returned channel is THE transport the step gossips through — pass it
    to ``init_train_state`` / ``ensure_channel_state`` so the TrainState's
    ``"channel"`` bucket matches the step's expectations by construction.
    """
    n_nodes = 1
    for a in node_axes:
        n_nodes *= mesh.shape[a]
    tp = mesh.shape[model_axis]
    tp_ctx = TPContext(axis=model_axis, size=tp, in_shard_map=True)
    rt = tcfg.runtime

    topology = build_topology(tcfg.topology, n_nodes)
    if (
        tcfg.algorithm == "decentlam"
        and topology.period > 1
        and tcfg.momentum > 0.5
    ):
        import warnings

        warnings.warn(
            "DecentLaM's convergence analysis assumes a static mixing matrix"
            " (paper Assumption A.3); with time-varying topologies the"
            f" momentum on the gossip penalty can resonate at beta="
            f"{tcfg.momentum} > 0.5. Consider beta <= 0.5 or a static"
            " topology (see DESIGN.md §5).",
            stacklevel=2,
        )
    opt = make_optimizer(tcfg.opt_config())
    lr_fn = build_schedule(tcfg.schedule)

    # one static plane layout shared by the step, the state initializer
    # and the resume path.  At tp > 1 the layout is sharded: its segments
    # carry local per-mesh-column shapes, so the in-shard_map pack/unpack
    # below operate on exactly the rank's shard rows and the stacked plane
    # state splits over the model axis (P(model, None) per node, see
    # train_state._plane_pspec).
    layout = model_plane_layout(cfg, tp, model_axis)

    tracker = None
    if tcfg.sparse_gossip:
        if tp > 1:
            # the sparse channels' per-round volume telemetry is a
            # replicated scalar, but at tp > 1 each mesh column's dirty-row
            # masks (hence its sparse egress) differ — surfacing per-rank
            # volume needs the wire-compaction rework tracked in ROADMAP
            raise NotImplementedError(
                "sparse_gossip x tp > 1 is not supported yet: per-rank "
                "dirty masks make the volume telemetry vary over the model "
                "axis; use dense gossip at tp > 1"
            )
        from ..sparse import RowTracker

        tracker = RowTracker.for_model(
            layout, layout.local_template(),
            tied_embeddings=cfg.tie_embeddings,
        )

    gossip = build_gossip_channel(
        tcfg, topology, node_axes, gossips_per_step=opt.gossips_per_step
    )
    mean = make_psum_mean(node_axes, n_nodes)

    def loss_fn(params, batch):
        # the scope names the model's forward, its backward (as
        # transpose(jvp(model))) and remat's recompute in the HLO metadata
        with jax.named_scope("model"):
            return T.forward_loss(
                params, batch, cfg, tp_ctx, rt, collect_rows=tcfg.sparse_gossip
            )

    def replicated_over_model(spec: P, x: jax.Array) -> jax.Array:
        """``x`` typed as its out_spec claims on the model axis.

        A plane bucket packs replicated leaves beside model-sharded ones, so
        after unpack the replicated params (and anything counted from the
        bucket, like the sparse channel's volume telemetry) carry the
        bucket's model-axis variance although every rank computed the same
        values.  For such a leaf whose spec does not name the model axis,
        the psum of model rank 0's copy (exact: the other terms are zeros)
        proves the replication to the vma check.  At tp == 1 it is a psum
        over one member, which compiles away.
        """
        if model_axis in _spec_axes(spec) or model_axis not in jax.typeof(x).vma:
            return x
        if tp > 1:
            x = jnp.where(
                jax.lax.axis_index(model_axis) == 0, x, jnp.zeros_like(x)
            )
        return jax.lax.psum(x, model_axis)

    def grads_of(params, batch):
        accum = tcfg.grad_accum
        if accum == 1:
            (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            return g, loss, metrics

        def reshape(x):
            b = x.shape[0]
            assert b % accum == 0, (b, accum)
            return x.reshape(accum, b // accum, *x.shape[1:])

        mbs = jax.tree.map(reshape, batch)

        def micro(carry, mb):
            gsum, lsum = carry
            (l, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            gsum = jax.tree.map(
                lambda a, b_: a + b_.astype(jnp.float32) / accum, gsum, g
            )
            return (gsum, lsum + l / accum), metrics

        # zero carries must match the grads' shard_map variance exactly:
        # grads mirror param variance (vma-aware AD inserts the psums), and
        # the loss varies over the node axes (it is per-node data).
        g0 = jax.tree.map(lambda x: (x * 0).astype(jnp.float32), params)
        l0 = (batch["tokens"].ravel()[:1].sum() * 0).astype(jnp.float32)
        (g, loss), metrics = jax.lax.scan(micro, (g0, l0), mbs)
        # mean over the microbatch axis only: scalars stay scalars and the
        # (accum, Lg, E) row-info hit stacks reduce to (Lg, E) microbatch
        # unions (any nonzero mean -> hit)
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        return g, loss, metrics

    def step_fn(state: Tree, batch: Tree):
        params = jax.tree.map(lambda x: x[0], state["params"])
        opt_state = jax.tree.map(lambda x: x[0], state["opt"])
        comp_state = jax.tree.map(lambda x: x[0], state["channel"])
        step_idx = state["step"]
        lr = lr_fn(step_idx)

        grads, loss, metrics = grads_of(params, batch)
        return update_tail(
            batch, params, opt_state, comp_state, step_idx, lr, grads, loss,
            metrics,
        )

    @jax.named_scope("update_tail")
    def update_tail(
        batch, params, opt_state, comp_state, step_idx, lr, grads, loss, metrics
    ):
        """Everything after the gradient: finite guard, planes, update
        kernel and gossip, metric reductions."""
        # finite guard: when the local grad norm goes non-finite, the update
        # runs with zero grads (the gossip payload this round stays finite,
        # so neighbors keep mixing clean iterates) and the optimizer state
        # comes back unchanged (momentum/EF frozen — a poisoned step must
        # not leak into the accumulators).  Params still take the g=0
        # update, i.e. the node keeps gossiping.  run_update applies it
        # (its ``ok``), folded into the update stages where it can.  The
        # decision is per-node; the psum over the model axis (at any tp,
        # free at tp == 1) makes every model shard agree, so the replicated
        # params cannot desync and the guard is provably invariant over the
        # model axis, as the replicated out_specs require.
        finite = None
        if tcfg.finite_guard:
            gsq = jnp.float32(0.0)
            for gg in jax.tree.leaves(grads):
                gsq = gsq + jnp.sum(jnp.square(gg.astype(jnp.float32)))
            gsq = jax.lax.psum(gsq, model_axis)
            finite = jnp.isfinite(gsq)

        # row-info hit stacks are mask material, not scalar metrics: keep
        # them out of the pmean loop below and feed them to the tracker
        row_info = metrics.pop("_row_info", None)
        if tracker is not None:
            units = {"embed": batch["tokens"], **(row_info or {})}
            comp_state = gossip.mark(comp_state, tracker.step_masks(units))

        # pack once, run the whole tail + gossip on dtype-bucketed plane
        # buffers (O(buckets x stages) launches, O(buckets x edge-classes)
        # collectives), unpack the new params for the next forward.
        # Optimizer + channel state stay in plane form across steps; the
        # clip/LARS scalars come from the original trees (zeroed under the
        # guard, inside the norm reductions), as per-leaf norms cannot be
        # read off the packed buffers.
        ocfg = tcfg.opt_config()
        g32 = jax.tree.map(lambda gg: gg.astype(jnp.float32), grads)
        scalars = plane_scalars(ocfg, layout, params, zero_unless(finite, g32))
        new_x_pl, new_opt, comp_state = run_update(
            update_spec(ocfg),
            ocfg,
            x=layout.pack(params),
            g=layout.pack(g32, dtype=jnp.float32),
            state=opt_state,
            lr=lr,
            step_idx=step_idx,
            gossip=gossip,
            mean=mean,
            comp_state=comp_state,
            stage=make_plane_stage(tcfg.fused_impl),
            scalars=scalars,
            ok=finite,
        )
        new_params = layout.unpack(new_x_pl, like=params)

        # replicated scalar metrics
        out_metrics = {
            "loss": jax.lax.pmean(loss, node_axes),
            "lr": lr,
            # fleet-wide count of nodes whose update was skipped by the
            # finite guard this step (0.0 when the guard is off)
            "skipped_nonfinite": jax.lax.psum(
                jnp.float32(0.0) if finite is None else jnp.float32(~finite),
                node_axes,
            ),
            # fleet-worst consensus gap this round (0 on undelayed
            # channels) — the signal the serving publisher gates on; the
            # per-node vector is recovered host-side from the channel
            # state via core.gossip.fleet_node_gaps
            "gossip_gap": jax.lax.pmax(
                jnp.float32(gossip.node_gaps(comp_state)), node_axes
            ),
            **{k: jax.lax.pmean(v, node_axes) for k, v in metrics.items()},
        }
        if tcfg.track_consensus:
            out_metrics["consensus_sq"] = _consensus_metric(
                new_params, node_axes, n_nodes, model_axis
            )

        new_state = {
            "step": step_idx + 1,
            "params": jax.tree.map(lambda x: x[None], new_params),
            "opt": jax.tree.map(lambda x: x[None], new_opt),
            "channel": jax.tree.map(lambda x: x[None], comp_state),
        }
        new_state = jax.tree.map(
            replicated_over_model, sspecs, new_state,
            is_leaf=lambda x: isinstance(x, P),
        )
        return new_state, out_metrics

    sspecs = stacked_state_specs(
        cfg, opt, tp, node_axes, model_axis, gossip, layout
    )
    bspecs = batch_specs(cfg, node_axes)
    mspecs = {"loss": P(), "lr": P(), "gossip_gap": P(), "xent": P(),
              "moe_load_balance": P(), "moe_router_z": P(),
              "skipped_nonfinite": P()}
    if cfg.moe:  # the expert layer's counters (models/moe.py)
        mspecs.update(moe_dropped=P(), moe_max_load=P())
    if tcfg.track_consensus:
        mspecs["consensus_sq"] = P()

    all_axes = set(node_axes) | {model_axis}
    step_sm = jax.shard_map(
        step_fn,
        mesh=mesh,
        in_specs=(sspecs, bspecs),
        out_specs=(sspecs, mspecs),
        axis_names=all_axes,
    )
    return jax.jit(step_sm, donate_argnums=(0,)), sspecs, bspecs, gossip
