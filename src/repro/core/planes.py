"""Flat parameter planes: dtype-bucketed contiguous views of a pytree.

An update run leaf by leaf would pay one kernel launch per pytree leaf per
update stage and one collective per leaf per gossip edge class — for the
model-zoo configs that is hundreds of dispatches per step, each with its
own padding to the ``(rows, 1024)`` tile.  A :class:`PlaneLayout` collapses
that: the
whole tree is packed **once** into one contiguous ``(rows, LANES)`` buffer
per dtype bucket, with static per-leaf segment metadata (row offsets,
shapes, sizes) chosen so that

* every leaf starts at a row boundary (``LANES``-element granularity — no
  leaf straddles a tile row, so a row belongs to exactly one leaf), and
* every bucket's total row count is a multiple of 64 (the fused-update
  kernel's block height, itself a multiple of the f32/bf16 min-tile
  sublane counts 8/16 — exact-grid blocks, so no block of the plane
  kernel is partial),

so the fused-update engine runs **one** ``pallas_call`` per stage per
bucket and the gossip channels ship **one** buffer per bucket per edge
class.  Padding is zero-filled; all the engine's elementwise stage math
maps zeros to zeros (``safe_lr`` clamps the divisions), so padded rows
stay inert and :meth:`PlaneLayout.unpack` never reads them.

Per-leaf quantities (the LARS trust ratio) are carried as *row-indexed
segment scalars*: :meth:`PlaneLayout.row_scalars` scatters a tree of
per-leaf scalars to a ``(rows, 1)`` column per bucket using the static
row→segment map, which broadcasts through the same
``pre_math``/``post_math`` expressions the per-leaf oracle uses (and rides
into the Pallas plane kernel as a narrow VMEM operand).

:func:`plane_scalars` computes the gradient-preprocessing scalars on the
**original trees** with the exact :func:`~repro.core.update_spec.grad_scalars`
code, then converts only the per-leaf LARS tree to row form — so the
clip/LARS scalars of the plane path are bit-identical to the per-leaf
oracle's by construction (a segment-reduction over planes would change the
summation order).

Layouts are static (built from shapes/dtypes only, ``jax.eval_shape``
friendly) and hashable-by-identity; ``pack``/``unpack`` are pure jnp and
trace under jit.  A ``leading`` axis count supports the stacked ``(n,
...)`` reference layout: build the layout from the per-node template and
pack with ``leading=1``.

**Sharded layouts (tensor parallelism).**  ``build(template, tp=k,
shardings=specs)`` plans a *per-mesh-column local* layout: for each leaf
the ``PartitionSpec`` names which dim (if any) is sharded over the model
axis, and the segment records the **local** shard shape (global dim ÷ tp)
next to the global one.  Replicated leaves pack identically on every
rank; sharded leaves occupy local rows only, so each TP rank's bucket is
a fully valid ``(rows, LANES)`` plane — ``ROW_MULTIPLE``-aligned like the
``tp == 1`` case, which is what keeps the fused kernel's 64-row block
grid (and hence bit-exactness) intact per rank.  The *global* (stacked
shard) form concatenates the tp per-rank packs along the row axis:
``pack_global`` emits ``(tp * rows, LANES)`` buffers sliceable by
``P(model_axis, None)``, so inside shard_map every rank sees exactly its
local bucket and all the local-tree machinery here (``pack``/``unpack``,
``row_scalars``, ``host_pack``/``view_unpack``) applies unchanged to the
local template.  ``unpack_global`` inverts it back to the global tree;
``global_layout()`` gives the unsharded layout of the global template for
consumers (checkpoint reconciliation, the serving publisher) that need
the wire/snapshot format to stay rank-free.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any

__all__ = ["LANES", "ROW_MULTIPLE", "Segment", "PlaneLayout", "plane_scalars"]

LANES = 1024  # lane width of the fused-update tile (= 8 x 128 VPU lanes)
ROW_MULTIPLE = 64  # bucket row totals pad to the kernel block height


@dataclasses.dataclass(frozen=True)
class Segment:
    """One leaf's slot inside a bucket plane (static metadata).

    ``shape`` is the **local** per-rank leaf shape — identical to the
    global shape for replicated leaves and for ``tp == 1`` layouts; for
    leaves sharded over the model axis it is the global shape with
    ``shard_axis`` divided by ``tp``.  All row arithmetic (``row_start``,
    ``rows``, ``size``) is in local terms, so every consumer of the local
    plane form reads ``shape`` and never needs to know about sharding.
    """

    index: int  # leaf position in the template's flatten order
    shape: tuple[int, ...]  # LOCAL per-rank leaf shape (leading axes excluded)
    dtype: Any  # template dtype (unpack's default cast target)
    row_start: int  # first plane row of this leaf
    rows: int  # ceil(size / LANES)
    size: int  # true element count (rows * LANES - size is zero pad)
    # sharding metadata — defaults describe an unsharded segment
    global_shape: tuple[int, ...] | None = None  # None -> same as ``shape``
    shard_axis: int | None = None  # dim split over the model axis (or None)

    @property
    def full_shape(self) -> tuple[int, ...]:
        """Global (unsharded) leaf shape."""
        return self.shape if self.global_shape is None else self.global_shape


def _bucket_key(dtype) -> str:
    return jnp.dtype(dtype).name


def _shard_axis_of(spec, model_axis: str) -> int | None:
    """Dim of a ``PartitionSpec`` sharded over ``model_axis`` (or None).

    The repo's param specs put at most one mesh axis per dim and shard at
    most one dim per leaf over the model axis; the first match wins.
    """
    if spec is None:
        return None
    for dim, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        if any(n == model_axis for n in names if n is not None):
            return dim
    return None


class PlaneLayout:
    """Static packing plan for one pytree template (see module docstring)."""

    def __init__(self, treedef, segments: dict[str, tuple[Segment, ...]],
                 rows: dict[str, int], *, tp: int = 1,
                 model_axis: str = "model"):
        self.treedef = treedef
        self.segments = segments
        self.rows = rows  # per-bucket LOCAL row totals (ROW_MULTIPLE aligned)
        self.tp = tp  # mesh-column count the local shapes were planned for
        self.model_axis = model_axis
        self.n_leaves = treedef.num_leaves
        # row -> segment position within the bucket; tail-pad rows alias
        # segment 0 (their data is zero, so any scalar they pick up is inert)
        self._row_pos: dict[str, np.ndarray] = {}
        for key, segs in segments.items():
            pos = np.zeros(rows[key], dtype=np.int32)
            for p, seg in enumerate(segs):
                pos[seg.row_start: seg.row_start + seg.rows] = p
            self._row_pos[key] = pos

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, template: Tree, *, tp: int = 1, shardings: Tree | None = None,
              model_axis: str = "model") -> "PlaneLayout":
        """Plan the packing for ``template`` (arrays or ShapeDtypeStructs;
        only ``.shape``/``.dtype`` are read).

        ``template`` always carries **global** shapes.  At ``tp == 1`` the
        plan is the flat unsharded layout.  At ``tp > 1``, ``shardings``
        (a tree of ``PartitionSpec`` matching ``template``) decides which
        leaves are sharded over ``model_axis``; those segments get local
        shapes (sharded dim ÷ tp — must divide exactly, the model configs
        pad vocab/heads to tp) while replicated leaves keep their global
        shape on every rank.
        """
        leaves, treedef = jax.tree.flatten(template)
        if tp > 1 and shardings is None:
            raise ValueError(
                "PlaneLayout.build(tp > 1) needs `shardings` (PartitionSpec "
                "tree matching the template) to locate the model axis"
            )
        spec_leaves = (
            treedef.flatten_up_to(shardings) if shardings is not None else None
        )
        segs: dict[str, list[Segment]] = {}
        for i, leaf in enumerate(leaves):
            key = _bucket_key(leaf.dtype)
            bucket = segs.setdefault(key, [])
            start = bucket[-1].row_start + bucket[-1].rows if bucket else 0
            gshape = tuple(leaf.shape)
            ax = (
                _shard_axis_of(spec_leaves[i], model_axis)
                if tp > 1 else None
            )
            if ax is None:
                lshape = gshape
            else:
                if gshape[ax] % tp != 0:
                    raise ValueError(
                        f"leaf {i}: global dim {ax} of {gshape} is sharded "
                        f"over {model_axis!r} but not divisible by tp={tp}"
                    )
                lshape = gshape[:ax] + (gshape[ax] // tp,) + gshape[ax + 1:]
            size = int(np.prod(lshape)) if lshape else 1
            rows = max(1, -(-size // LANES))
            bucket.append(Segment(i, lshape, jnp.dtype(leaf.dtype),
                                  start, rows, size, gshape, ax))
        rows = {
            key: -(-(b[-1].row_start + b[-1].rows) // ROW_MULTIPLE) * ROW_MULTIPLE
            for key, b in segs.items()
        }
        return cls(treedef, {k: tuple(v) for k, v in segs.items()}, rows,
                   tp=tp, model_axis=model_axis)

    @property
    def buckets(self) -> tuple[str, ...]:
        """Bucket keys in the planes dict's (sorted) pytree order."""
        return tuple(sorted(self.segments))

    def plane_shapes(self, dtype=None) -> dict[str, jax.ShapeDtypeStruct]:
        """Abstract plane buffers (``dtype=None`` keeps each bucket's own)."""
        return {
            key: jax.ShapeDtypeStruct(
                (self.rows[key], LANES),
                jnp.dtype(dtype) if dtype is not None else jnp.dtype(key),
            )
            for key in self.segments
        }

    # -- sharded (tensor-parallel) views ------------------------------------

    @property
    def sharded(self) -> bool:
        """True when this layout plans per-mesh-column local shards."""
        return self.tp > 1

    def local_template(self) -> Tree:
        """``ShapeDtypeStruct`` tree of one rank's LOCAL leaves (== the
        global template at ``tp == 1``)."""
        out: list = [None] * self.n_leaves
        for segs in self.segments.values():
            for seg in segs:
                out[seg.index] = jax.ShapeDtypeStruct(seg.shape, seg.dtype)
        return self.treedef.unflatten(out)

    def global_template(self) -> Tree:
        """``ShapeDtypeStruct`` tree of the GLOBAL (unsharded) leaves."""
        out: list = [None] * self.n_leaves
        for segs in self.segments.values():
            for seg in segs:
                out[seg.index] = jax.ShapeDtypeStruct(seg.full_shape, seg.dtype)
        return self.treedef.unflatten(out)

    def global_layout(self) -> "PlaneLayout":
        """Unsharded layout over the global template (``self`` at tp == 1).

        This is the rank-free plane form consumers outside the mesh see:
        the serving publisher packs snapshots with it so ``view_unpack``
        leaves stay contiguous, and checkpoint reconciliation uses it as
        the common ground between layouts planned at different tp.
        """
        if self.tp == 1:
            return self
        cached = getattr(self, "_global_layout_cache", None)
        if cached is None:
            cached = PlaneLayout.build(self.global_template())
            self._global_layout_cache = cached
        return cached

    def shard_slice(self, tree: Tree, rank, *, leading: int = 0) -> Tree:
        """``rank``'s local shard of a GLOBAL tree.

        Replicated leaves pass through unsliced; sharded leaves are cut
        along their ``shard_axis``.  ``rank`` may be a traced value (the
        slice lowers to ``dynamic_slice``).
        """
        if self.tp == 1:
            return tree
        leaves = list(self.treedef.flatten_up_to(tree))
        for segs in self.segments.values():
            for seg in segs:
                if seg.shard_axis is None:
                    continue
                n = seg.shape[seg.shard_axis]
                leaves[seg.index] = jax.lax.dynamic_slice_in_dim(
                    jnp.asarray(leaves[seg.index]), rank * n, n,
                    axis=seg.shard_axis + leading,
                )
        return self.treedef.unflatten(leaves)

    def pack_global(self, tree: Tree, *, dtype=None, leading: int = 0,
                    impl: str | None = None) -> dict:
        """Pack a GLOBAL tree into stacked shard planes.

        At ``tp == 1`` this is exactly :meth:`pack`.  At ``tp > 1`` each
        bucket is the row-concatenation of the tp per-rank local packs —
        ``(tp * rows[key], LANES)`` with rank ``r`` owning the row block
        ``[r * rows, (r + 1) * rows)`` — so a ``P(model_axis, None)``
        spec hands every shard_map rank exactly its local
        ``(rows, LANES)`` bucket.  Replicated leaves appear, identically,
        in every rank block.
        """
        if self.tp == 1:
            return self.pack(tree, dtype=dtype, leading=leading, impl=impl)
        packs = [
            self.pack(self.shard_slice(tree, r, leading=leading),
                      dtype=dtype, leading=leading, impl=impl)
            for r in range(self.tp)
        ]
        return {
            key: jnp.concatenate([p[key] for p in packs], axis=leading)
            for key in packs[0]
        }

    def unpack_global(self, planes: dict, *, like: Tree | None = None,
                      dtype=None, leading: int = 0) -> Tree:
        """Inverse of :meth:`pack_global`: stacked shard planes -> GLOBAL
        tree.  Splits each bucket into its tp rank blocks, unpacks each to
        the local template, and concatenates sharded leaves along their
        shard axis (replicated leaves are taken from rank 0)."""
        if self.tp == 1:
            return self.unpack(planes, like=like, dtype=dtype, leading=leading)
        ranks = []
        for r in range(self.tp):
            block = {
                key: jax.lax.slice_in_dim(
                    planes[key], r * self.rows[key], (r + 1) * self.rows[key],
                    axis=leading,
                )
                for key in self.segments
            }
            ranks.append(self.treedef.flatten_up_to(
                self.unpack(block, dtype=dtype, leading=leading)
            ))
        like_leaves = (
            self.treedef.flatten_up_to(like) if like is not None else None
        )
        out: list = [None] * self.n_leaves
        for segs in self.segments.values():
            for seg in segs:
                i = seg.index
                if seg.shard_axis is None:
                    v = ranks[0][i]
                else:
                    v = jnp.concatenate(
                        [rk[i] for rk in ranks], axis=seg.shard_axis + leading
                    )
                if dtype is None:
                    v = v.astype(
                        like_leaves[i].dtype if like_leaves is not None
                        else seg.dtype
                    )
                out[i] = v
        return self.treedef.unflatten(out)

    # -- pack / unpack ------------------------------------------------------

    @jax.named_scope("plane_pack")
    def pack(self, tree: Tree, *, dtype=None, leading: int = 0,
             impl: str | None = None) -> dict:
        """Pack ``tree`` (structure of the template) into plane buffers.

        ``dtype`` casts every buffer (pass ``jnp.float32`` for gradient /
        momentum / payload trees whose leaves don't carry the template
        dtypes); ``leading`` preserves that many leading axes per leaf
        (the stacked ``(n, ...)`` layout packs with ``leading=1``).

        ``impl`` selects the lowering — both produce identical values:

        * ``"concat"`` — per-leaf zero-pad + one concatenate per bucket.
          The natural form on accelerators (pure DMA memcpy, no extra
          constants).
        * ``"gather"``  — concatenate the *raw* leaves densely (memcpy
          fast path), then expand to the padded layout with one static
          gather.  XLA's CPU concatenate emitter falls off a cliff (up to
          ~10x, erratically across shapes) when zero-pad operands are
          fused into a many-operand concat; the gather form is uniformly
          fast there at the cost of an O(elements) int32 index constant.

        Default: ``"gather"`` on the CPU backend, ``"concat"`` elsewhere.
        """
        if impl is None:
            impl = "gather" if jax.default_backend() == "cpu" else "concat"
        leaves = self.treedef.flatten_up_to(tree)
        planes: dict[str, jax.Array] = {}
        for key, segs in self.segments.items():
            lead = tuple(np.shape(leaves[segs[0].index])[:leading])
            for seg in segs:
                assert np.shape(leaves[seg.index])[leading:] == seg.shape, (
                    np.shape(leaves[seg.index]), seg,
                )
            if impl == "gather":
                dense = jnp.concatenate(
                    [
                        jnp.asarray(leaves[s.index]).reshape(lead + (-1,))
                        for s in segs
                    ],
                    axis=leading,
                )
                dz = jnp.concatenate(
                    [dense, jnp.zeros(lead + (1,), dense.dtype)], axis=leading
                )
                # NOT indices_are_sorted: pad slots point at the zero slot
                # *past* the dense end, so the map is non-monotonic between
                # segments — claiming sortedness would be UB on backends
                # whose gather emitters exploit it
                buf = jnp.take(
                    dz, jnp.asarray(self._gather_idx(key)), axis=leading,
                    mode="clip",
                ).reshape(lead + (self.rows[key], LANES))
            else:
                parts = []
                for seg in segs:
                    flat = jnp.asarray(leaves[seg.index]).reshape(lead + (-1,))
                    pad = seg.rows * LANES - seg.size
                    if pad:
                        flat = jnp.pad(
                            flat, [(0, 0)] * leading + [(0, pad)]
                        )
                    parts.append(flat.reshape(lead + (seg.rows, LANES)))
                tail = self.rows[key] - (segs[-1].row_start + segs[-1].rows)
                if tail:
                    parts.append(jnp.zeros(lead + (tail, LANES), parts[0].dtype))
                buf = jnp.concatenate(parts, axis=leading)
            if dtype is not None:
                buf = buf.astype(dtype)
            planes[key] = buf
        return planes

    def _gather_idx(self, key: str) -> np.ndarray:
        """Static padded-position -> dense-position map of one bucket
        (pad positions point one past the dense end — a zero slot)."""
        cache = getattr(self, "_gather_cache", None)
        if cache is None:
            cache = {}
            object.__setattr__(self, "_gather_cache", cache)
        if key not in cache:
            segs = self.segments[key]
            total = sum(s.size for s in segs)
            idx = np.full(self.rows[key] * LANES, total, np.int32)
            off = 0
            for s in segs:
                start = s.row_start * LANES
                idx[start: start + s.size] = np.arange(
                    off, off + s.size, dtype=np.int32
                )
                off += s.size
            cache[key] = idx
        return cache[key]

    @jax.named_scope("plane_unpack")
    def unpack(self, planes: dict, *, like: Tree | None = None,
               dtype=None, leading: int = 0) -> Tree:
        """Slice the plane buffers back into the template structure.

        Each leaf casts to ``dtype`` when given, else to ``like``'s leaf
        dtype, else to the template dtype recorded in its segment.
        """
        like_leaves = (
            self.treedef.flatten_up_to(like) if like is not None else None
        )
        out: list = [None] * self.n_leaves
        for key, segs in self.segments.items():
            buf = planes[key]
            lead = buf.shape[:leading]
            for seg in segs:
                sl = jax.lax.slice_in_dim(
                    buf, seg.row_start, seg.row_start + seg.rows, axis=leading
                )
                flat = sl.reshape(lead + (-1,))[..., : seg.size]
                if dtype is not None:
                    dt = dtype
                elif like_leaves is not None:
                    dt = like_leaves[seg.index].dtype
                else:
                    dt = seg.dtype
                out[seg.index] = flat.reshape(lead + seg.shape).astype(dt)
        return self.treedef.unflatten(out)

    # -- host-side pack / zero-copy views (the serving handoff path) --------

    def host_pack(self, tree: Tree, out: dict | None = None) -> dict:
        """Pack ``tree`` into **host** (numpy) plane buffers.

        The device ``pack`` builds a fresh traced buffer per call; the
        serving publisher instead wants to refill a *preallocated* host
        buffer (its standby half — readers keep views on the active half
        while this writes).  Pass ``out`` to reuse buffers; padding rows
        are zeroed once at allocation and never written again (segment
        writes cover exactly ``seg.size`` elements).

        Leaves may be jax arrays (fetched to host, one transfer per leaf)
        or numpy arrays.  Dtypes must match the template's — the plane
        buffer *is* the byte-exact concatenation of the leaves.
        """
        leaves = self.treedef.flatten_up_to(tree)
        if out is None:
            out = {
                key: np.zeros((self.rows[key], LANES), np.dtype(key))
                for key in self.segments
            }
        for key, segs in self.segments.items():
            buf = out[key]
            assert buf.shape == (self.rows[key], LANES) and buf.flags.c_contiguous
            flat = buf.reshape(-1)
            for seg in segs:
                leaf = np.asarray(leaves[seg.index])
                assert leaf.dtype == seg.dtype, (leaf.dtype, seg)
                start = seg.row_start * LANES
                flat[start: start + seg.size] = leaf.reshape(-1)
        return out

    def view_unpack(self, planes: dict) -> Tree:
        """Zero-copy **views** of host plane buffers in template structure.

        Each leaf is a read-only numpy view sliced out of the contiguous
        ``(rows, LANES)`` buffer via the static segment metadata — no bytes
        move (``np.shares_memory(leaf, planes[bucket])`` holds for every
        leaf).  This is the serving hot path: a published snapshot hands
        the whole parameter tree to the request scheduler in O(leaves)
        metadata work instead of O(bytes) copies.  The views alias the
        buffer, so they are valid exactly as long as the buffer is not
        rewritten (the publisher's double buffer guarantees one publish of
        grace).  Bit-exactness with :meth:`unpack` of the same planes is
        pinned in ``tests/test_serve_publisher.py`` and spot-checked at
        publish time when the publisher's consistency check is on.
        """
        out: list = [None] * self.n_leaves
        for key, segs in self.segments.items():
            buf = np.asarray(planes[key])
            assert buf.flags.c_contiguous, "plane buffers must be contiguous"
            flat = buf.reshape(-1)
            for seg in segs:
                start = seg.row_start * LANES
                v = flat[start: start + seg.size].reshape(seg.shape)
                v.flags.writeable = False
                out[seg.index] = v
        return self.treedef.unflatten(out)

    # -- per-leaf scalars as row-indexed segment scalars --------------------

    def row_scalars(self, scalar_tree: Tree) -> dict:
        """A tree of per-leaf scalars -> ``{bucket: (rows, 1) f32}`` columns.

        The static row→segment map scatters each leaf's scalar across its
        rows; broadcasting ``(rows, 1) * (rows, LANES)`` then applies it
        elementwise exactly like the per-leaf oracle's scalar multiply.
        """
        vals = self.treedef.flatten_up_to(scalar_tree)
        out = {}
        for key, segs in self.segments.items():
            col = jnp.stack(
                [jnp.asarray(vals[s.index], jnp.float32).reshape(()) for s in segs]
            )
            out[key] = col[self._row_pos[key]][:, None]
        return out


def plane_scalars(cfg, layout: PlaneLayout, x: Tree, g: Tree) -> dict:
    """Gradient-preprocessing scalars for the plane path.

    Runs the exact per-leaf :func:`~repro.core.update_spec.grad_scalars`
    on the *original* trees (so ``gs`` and the LARS ratios are
    bit-identical to the per-leaf oracle), then converts the per-leaf LARS
    tree to row-indexed columns that broadcast over the plane buffers.
    Feed the result to ``run_update(..., scalars=...)`` together with
    plane-packed operands.
    """
    from .update_spec import grad_scalars

    s = dict(grad_scalars(cfg, x, g))
    # grad_scalars returns "r" as a per-leaf tree exactly when the LARS
    # family is active (structural check, so the gating predicate stays in
    # one place — update_spec); scalars pass through untouched
    r = s.get("r")
    if r is not None and jax.tree.structure(r) == layout.treedef:
        s["r"] = layout.row_scalars(r)
    return s
