"""TrainState: stacked per-node parameters + optimizer + channel state.

Every leaf carries a leading *node* axis of size ``n_nodes`` — one model
replica per decentralized node (DESIGN.md §4).  The ``"channel"`` bucket is
the gossip transport's state (:class:`repro.core.gossip.GossipChannel`):
compression error-feedback, delay ring buffers, telemetry — one
checkpointable node whose structure/specs come from the channel itself.
The parameters stay in tree form (the forward pass consumes them by name);
the optimizer and channel buckets hold flat planes of the model's
:func:`model_plane_layout`.  ``init_train_state`` builds the state on-device
through jit-with-out-shardings so each device only ever materializes its
own shard (mandatory at 8B x 32 replicas); ``abstract_train_state`` gives
its shapes (eval_shape, zero allocation).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.gossip import GossipChannel
from ..core.optimizers import Optimizer
from ..core.planes import PlaneLayout
from ..models import transformer as T

Tree = Any

__all__ = [
    "stacked_param_specs",
    "stacked_state_specs",
    "make_train_state_fn",
    "init_train_state",
    "abstract_train_state",
    "ensure_channel_state",
    "model_plane_layout",
    "reconcile_plane_state",
]


def model_plane_layout(
    cfg: ModelConfig, tp: int = 1, model_axis: str = "model"
) -> PlaneLayout:
    """The flat-plane layout of this model's per-node parameter tree.

    The train step keeps the optimizer and channel hot state packed in
    this layout across steps; the step, the state
    initializer and the resume path must all derive it from the same
    template, which this helper pins (abstract — no allocation).  At
    ``tp > 1`` the layout is sharded: the model's ``param_specs`` decide
    which leaves split over ``model_axis``, and every mesh column gets
    its own local ``(rows, LANES)`` buckets (see
    :class:`~repro.core.planes.PlaneLayout`).
    """
    abs_params = jax.eval_shape(
        lambda k: T.init_params(k, cfg, tp), jax.random.key(0)
    )
    if tp == 1:
        return PlaneLayout.build(abs_params)
    return PlaneLayout.build(
        abs_params, tp=tp, shardings=T.param_specs(cfg, tp, model_axis),
        model_axis=model_axis,
    )


def _plane_pspec(layout: PlaneLayout) -> Tree:
    """Per-node PartitionSpec tree of a plane dict.

    A sharded layout stacks the tp per-rank row blocks along the row axis,
    so the buffer splits over the model axis and each mesh column sees
    exactly its local bucket inside shard_map.  At tp == 1 the split over
    the size-1 axis moves nothing, but it still says what the step can
    prove: a bucket packs model-sharded and replicated leaves together, so
    it varies over the model axis."""
    return {key: P(layout.model_axis, None) for key in layout.segments}


def _prepend_axis(spec_tree: Tree, axes) -> Tree:
    return jax.tree.map(
        lambda s: P(axes, *s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def stacked_param_specs(cfg: ModelConfig, tp: int, node_axes, model_axis="model"):
    return _prepend_axis(T.param_specs(cfg, tp, model_axis), node_axes)


def stacked_state_specs(
    cfg: ModelConfig, opt: Optimizer, tp: int, node_axes, model_axis="model",
    channel: GossipChannel | None = None,
    plane_layout: PlaneLayout | None = None,
) -> Tree:
    """Specs for the full TrainState pytree (params + opt + channel state).

    The optimizer and channel buckets hold plane buffers of
    ``plane_layout`` (default: the model's own, :func:`model_plane_layout`)
    — one ``(rows, LANES)`` leaf per dtype bucket — while the parameters
    stay in tree form (the forward pass consumes them by name).
    """
    from ..core.optimizers import state_keys

    if plane_layout is None:
        plane_layout = model_plane_layout(cfg, tp, model_axis)
    pspec = T.param_specs(cfg, tp, model_axis)
    hot_spec = _plane_pspec(plane_layout)
    # every optimizer state bucket holds the params' planes
    opt_state_spec: Tree = {k: hot_spec for k in state_keys(opt.config)}
    channel_spec = channel.state_specs(hot_spec) if channel is not None else {}
    return {
        "step": P(),
        "params": _prepend_axis(pspec, node_axes),
        "opt": _prepend_axis(opt_state_spec, node_axes),
        "channel": _prepend_axis(channel_spec, node_axes),
    }


def make_train_state_fn(
    cfg: ModelConfig,
    opt: Optimizer,
    n_nodes: int,
    tp: int,
    channel: GossipChannel | None = None,
    plane_layout: PlaneLayout | None = None,
):
    """Pure init function (jit-able with out_shardings).

    The optimizer state buckets and the channel template are packed into
    f32 planes of ``plane_layout`` (default: the model's own) here — this
    is the *only* pack the hot state ever pays outside a checkpoint
    boundary; the train step keeps it in plane form from then on.
    """
    if plane_layout is None:
        plane_layout = model_plane_layout(cfg, tp)

    def init_fn(key):
        params = T.init_params(key, cfg, tp)

        def stack(x):
            return jnp.broadcast_to(x[None], (n_nodes,) + x.shape)

        sp = jax.tree.map(stack, params)
        opt_state = {
            k: plane_layout.pack_global(v, dtype=jnp.float32)
            for k, v in opt.init(params).items()
        }
        chan_template = plane_layout.pack_global(params, dtype=jnp.float32)
        opt_state = jax.tree.map(stack, opt_state)
        chan = (
            jax.tree.map(stack, channel.init(chan_template))
            if channel is not None
            else {}
        )
        return {
            "step": jnp.zeros((), jnp.int32),
            "params": sp,
            "opt": opt_state,
            "channel": chan,
        }

    return init_fn


def init_train_state(
    key,
    cfg: ModelConfig,
    opt: Optimizer,
    n_nodes: int,
    tp: int,
    *,
    mesh=None,
    node_axes=None,
    model_axis: str = "model",
    channel: GossipChannel | None = None,
    plane_layout: PlaneLayout | None = None,
):
    if plane_layout is None:
        plane_layout = model_plane_layout(cfg, tp, model_axis)
    init_fn = make_train_state_fn(cfg, opt, n_nodes, tp, channel, plane_layout)
    if mesh is None:
        return init_fn(key)
    specs = stacked_state_specs(
        cfg, opt, tp, node_axes, model_axis, channel, plane_layout
    )
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    return jax.jit(init_fn, out_shardings=shardings)(key)


def _merge_channel(abstract: Tree, old: Tree) -> Tree:
    """Prefer restored leaves whose shape/dtype match the abstract spec;
    materialize zeros for anything missing or reshaped (channel state is
    zero-initialized by construction, so zeros == ``channel.init``)."""
    if isinstance(abstract, dict):
        if not isinstance(old, dict):
            old = {}
        return {k: _merge_channel(v, old.get(k)) for k, v in abstract.items()}
    if old is not None:
        old = jnp.asarray(old)
        if old.shape == abstract.shape and old.dtype == abstract.dtype:
            return old
    return jnp.zeros(abstract.shape, abstract.dtype)


def _subtree_matches(abstract: Tree, old: Tree) -> bool:
    if old is None or jax.tree.structure(abstract) != jax.tree.structure(old):
        return False
    return all(
        jnp.asarray(o).shape == a.shape and jnp.asarray(o).dtype == a.dtype
        for a, o in zip(jax.tree.leaves(abstract), jax.tree.leaves(old))
    )


def ensure_channel_state(
    state: Tree,
    channel: GossipChannel | None,
    n_nodes: int,
    plane_layout: PlaneLayout,
) -> Tree:
    """Reconcile a restored TrainState's ``"channel"`` bucket with the
    current channel's structure (its payload template: the params packed
    into ``plane_layout``'s f32 planes).

    Matching sub-nodes survive (compression error feedback and delay
    buffers resume bit-exactly on a same-shape restart); anything missing —
    pre-channel checkpoints, a newly enabled delay or telemetry, an elastic
    reshape that invalidated the buffers — is zero-initialized.  The
    expected structure comes from ``jax.eval_shape`` (no allocation; only
    the subtrees that actually re-init materialize zeros — a delayed
    channel's fresh ring buffers are ``n_nodes x (delay+1) x model`` f32,
    which must never be built just to be thrown away on a matching resume).
    Delay ring-buffer slots resume *atomically*: keeping a restored
    ``count`` while its ``hist`` re-inits (e.g. after a delay change
    resized the ring) would skip the warmup rule ``min(d, count)`` and mix
    all-zero payloads with full edge weight.
    """
    if channel is None:
        return {**state, "channel": {}}
    template = jax.eval_shape(
        lambda p: plane_layout.pack_global(
            jax.tree.map(lambda x: x[0], p), dtype=jnp.float32
        ),
        state["params"],
    )
    abstract = jax.eval_shape(
        lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_nodes,) + x.shape),
            channel.init(t),
        ),
        template,
    )
    old = state.get("channel", {})
    if not isinstance(old, dict):
        old = {}
    merged: Tree = {}
    for key, abs_v in abstract.items():
        old_v = old.get(key)
        if key == "delay":
            merged[key] = {
                slot_key: (
                    jax.tree.map(jnp.asarray, old_v[slot_key])
                    if isinstance(old_v, dict)
                    and _subtree_matches(abs_slot, old_v.get(slot_key))
                    else jax.tree.map(
                        lambda a: jnp.zeros(a.shape, a.dtype), abs_slot
                    )
                )
                for slot_key, abs_slot in abs_v.items()
            }
        else:
            merged[key] = _merge_channel(abs_v, old_v)
    return {**state, "channel": merged}


def _check_same_global_template(a: PlaneLayout, b: PlaneLayout) -> None:
    ta, tb = a.global_template(), b.global_template()
    if jax.tree.structure(ta) != jax.tree.structure(tb):
        raise ValueError(
            "checkpoint plane layout and current layout disagree on tree "
            "structure — the checkpoint was written for a different model"
        )
    for la, lb in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        if la.shape != lb.shape or la.dtype != lb.dtype:
            raise ValueError(
                f"global leaf mismatch between checkpoint plane layout and "
                f"current layout: {la.shape}/{la.dtype} vs "
                f"{lb.shape}/{lb.dtype} — tp-dependent padding "
                f"(vocab_padded / n_heads_padded) differs between the two "
                f"tp values, so the planes are not convertible"
            )


def reconcile_plane_state(
    state: Tree, plane_layout: PlaneLayout,
    stored_layout: PlaneLayout | None = None,
) -> Tree:
    """Bring a restored TrainState's optimizer bucket into the plane form
    of ``plane_layout``.

    Two conversions, per state key:

    * a tree-form bucket (written by a per-leaf update path that no longer
      exists) is packed, so such checkpoints still resume;
    * a plane-form bucket written at another tensor-parallel degree —
      ``stored_layout``, rebuilt from the V3 manifest's ``plane_tp`` —
      round-trips through the global tree (``stored.unpack_global`` ->
      ``current.pack_global``), so checkpoints written at ``tp=k`` restore
      at ``tp=1`` and vice versa, provided both tp values pad the model
      identically.  That global-template compatibility is asserted only
      when a plane actually needs converting: a tree-form bucket resumes
      across tp values regardless of padding differences, since no plane
      is ever interpreted through the wrong layout.

    A plane-form bucket is recognized by its top-level keys being the
    layout's dtype-bucket names (parameter trees never use dtype names as
    top-level keys).  Channel state is *not* converted — its structure is
    transport-internal (ring buffers sized by the payload), so a resume
    that changed it re-initializes it through :func:`ensure_channel_state`,
    exactly like any other structural change.  All optimizer buckets are
    f32 by construction, packed with the stacked node axis preserved.
    """
    if "opt" not in state:
        return state
    stored = stored_layout if stored_layout is not None else plane_layout
    buckets = set(plane_layout.segments)
    cross_tp = stored.tp != plane_layout.tp
    templates_checked = False
    new_opt: Tree = {}
    for k, v in state["opt"].items():
        is_plane = isinstance(v, dict) and set(v) == buckets
        if is_plane and not cross_tp:
            new_opt[k] = v
            continue
        if is_plane:
            if not templates_checked:
                _check_same_global_template(stored, plane_layout)
                templates_checked = True
            v = stored.unpack_global(v, dtype=jnp.float32, leading=1)
        new_opt[k] = plane_layout.pack_global(v, dtype=jnp.float32, leading=1)
    return {**state, "opt": new_opt}


def abstract_train_state(
    cfg: ModelConfig, opt: Optimizer, n_nodes: int, tp: int,
    channel: GossipChannel | None = None,
    plane_layout: PlaneLayout | None = None,
):
    """ShapeDtypeStruct pytree of the TrainState (compile-only input
    stand-in)."""
    init_fn = make_train_state_fn(cfg, opt, n_nodes, tp, channel, plane_layout)
    return jax.eval_shape(init_fn, jax.random.key(0))
