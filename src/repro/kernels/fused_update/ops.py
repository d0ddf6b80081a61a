"""Tree-level fused optimizer-update engine (jit'd wrappers).

``make_stage`` builds a stage executor with the same signature as
``update_spec.reference_stage`` but backed by the generic Pallas stage
kernel: every leaf is flattened, tiled to (rows, 1024), and updated in a
single HBM pass.  Feed it to ``update_spec.run_update`` to run *any* of the
eleven algorithms' update tails fused::

    from repro.core.update_spec import run_update, update_spec
    from repro.kernels.fused_update import make_stage

    x, state, comp = run_update(update_spec(cfg), cfg, ..., stage=make_stage())

``decentlam_update`` keeps the original single-algorithm entry point (the
Alg. 2 / eq. 17 tail) on top of the same engine.

``make_plane_stage`` is the flat fast path: operands arrive as
:class:`~repro.core.planes.PlaneLayout` buffers (one contiguous
``(rows, LANES)`` buffer per dtype bucket, every leaf row-aligned), so each
stage is **one** ``pallas_call`` per bucket instead of one per leaf — the
launch count per step drops from O(leaves x stages) to O(buckets x stages).
Per-leaf scalars (the LARS trust ratio) ride along as row-indexed segment
columns (see ``PlaneLayout.row_scalars``), not as per-leaf SMEM vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import planes as planes_mod
from ...core.update_spec import (
    MathCtx,
    _leaf_scalars,
    post_io,
    pre_io,
    reference_stage,
)
from .kernel import LANES, ROW_COLS, fused_stage_kernel

__all__ = [
    "make_stage",
    "fused_stage",
    "make_plane_stage",
    "fused_plane_stage",
    "decentlam_update",
    "LANES",
]

assert planes_mod.LANES == LANES, "plane layout and kernel tile disagree"


def _block_rows(n: int, dtypes) -> tuple[int, int]:
    """(block_rows, padded_rows) for a flat leaf of ``n`` elements.

    bf16 needs (16, 128) min tiles on TPU; f32 needs (8, 128).  Small leaves
    get a single min-height block, large ones (64, LANES) blocks.
    """
    min_sub = 16 if any(jnp.dtype(dt) == jnp.bfloat16 for dt in dtypes) else 8
    rows_raw = max(1, -(-n // LANES))
    br = 64 if rows_raw >= 64 else min_sub
    rows = -(-rows_raw // br) * br
    return br, rows


def _leaf_call(kind, op, ctx, leaf_ins, svec, out_dtypes, *, interpret):
    first = next(iter(leaf_ins.values()))
    shape, n = first.shape, first.size
    dtypes = [a.dtype for a in leaf_ins.values()] + list(out_dtypes.values())
    br, rows = _block_rows(n, dtypes)
    pad = rows * LANES - n

    def tile(a):
        if pad == 0 and a.ndim == 2 and a.shape == (rows, LANES):
            return a
        return jnp.pad(a.reshape(-1), (0, pad)).reshape(rows, LANES)

    tiled = {name: tile(a) for name, a in leaf_ins.items()}
    outs = fused_stage_kernel(
        kind, op, ctx, svec, tiled, out_dtypes, block_rows=br, interpret=interpret
    )
    return {
        name: o.reshape(-1)[:n].reshape(shape) for name, o in outs.items()
    }


def fused_stage(kind, op, ctx, operands, scalars, like_x, *, interpret=False):
    """Pallas-backed stage executor (signature of ``reference_stage``)."""
    names = tuple(operands)
    treedef = jax.tree.structure(operands[names[0]])
    cols = [treedef.flatten_up_to(operands[n]) for n in names]
    likes = treedef.flatten_up_to(like_x)
    per_leaf_s = _leaf_scalars(scalars, treedef, ctx)
    _, names_out = pre_io(op, ctx) if kind == "pre" else post_io(op)

    out_cols: dict[str, list] = {n: [] for n in names_out}
    for i in range(treedef.num_leaves):
        leaf_ins = {n: col[i] for n, col in zip(names, cols)}
        out_dtypes = {
            n: (likes[i].dtype if n == "x" else jnp.float32) for n in names_out
        }
        s = per_leaf_s[i]
        sg = jnp.asarray(s.get("sg", 1.0))
        if sg.ndim:
            raise NotImplementedError(
                "the fused stage takes a scalar staleness damping factor "
                "(per-node, as inside shard_map); stacked-layout "
                "staleness-aware runs use the reference stage"
            )
        svec = [jnp.asarray(s["lr"]), jnp.asarray(s["gs"]), jnp.asarray(s["r"]), sg]
        if ctx.guard:
            svec.append(jnp.asarray(s["ok"]))
        svec = jnp.stack(svec).astype(jnp.float32)
        res = _leaf_call(
            kind, op, ctx, leaf_ins, svec, out_dtypes, interpret=interpret
        )
        for name in names_out:
            out_cols[name].append(res[name])
    return {n: jax.tree.unflatten(treedef, col) for n, col in out_cols.items()}


def make_stage(impl: str = "pallas", *, interpret: bool = False):
    """Stage executor for ``run_update``: ref | pallas | pallas_interpret."""
    if impl == "ref":
        return reference_stage
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown fused impl {impl!r}")
    return functools.partial(
        fused_stage, interpret=interpret or impl == "pallas_interpret"
    )


def fused_plane_stage(kind, op, ctx, operands, scalars, like_x, *, interpret=False):
    """Whole-plane Pallas stage executor (signature of ``reference_stage``).

    Operands are plane trees — ``{bucket: (rows, LANES)}`` built by one
    :class:`~repro.core.planes.PlaneLayout` — so the "leaves" here are the
    dtype buckets and each stage issues exactly one ``pallas_call`` per
    bucket.  On a sharded layout (tp > 1) the buckets handed in are the
    mesh column's LOCAL shards; nothing here changes — local row totals
    are ``ROW_MULTIPLE``-aligned by construction, so the 64-row block grid
    is exact per rank and the launch count stays O(buckets x stages) *per
    rank*, matching the tp == 1 collapse.  The LARS trust ratio, when
    present, arrives as the layout's row-indexed segment columns
    (``{bucket: (rows, 1)}``) and is fed to the kernel as a narrow VMEM
    operand; ``gs``/``sg`` (and the guard's ``ok``) stay SMEM scalars.
    """
    names = tuple(operands)
    treedef = jax.tree.structure(operands[names[0]])
    cols = [treedef.flatten_up_to(operands[n]) for n in names]
    likes = treedef.flatten_up_to(like_x)
    _, names_out = pre_io(op, ctx) if kind == "pre" else post_io(op)

    sg = jnp.asarray(scalars.get("sg", 1.0))
    if sg.ndim:
        raise NotImplementedError(
            "the fused plane stage takes a scalar staleness damping factor "
            "(per-node, as inside shard_map); stacked-layout staleness-aware "
            "runs use the reference stage"
        )
    gs = jnp.asarray(scalars.get("gs", 1.0))
    r = scalars.get("r")
    r_cols = None
    if ctx.lars and r is not None and jax.tree.structure(r) == treedef:
        r_cols = treedef.flatten_up_to(r)
    r_scalar = jnp.asarray(1.0 if r_cols is not None or r is None else r)

    svec = [jnp.asarray(scalars["lr"]), gs, r_scalar, sg]
    if ctx.guard:
        svec.append(jnp.asarray(scalars["ok"]))
    svec = jnp.stack(svec).astype(jnp.float32)

    out_cols: dict[str, list] = {n: [] for n in names_out}
    for i in range(treedef.num_leaves):
        leaf_ins = {n: col[i] for n, col in zip(names, cols)}
        first = leaf_ins[names[0]]
        rows = first.shape[0]
        assert first.ndim == 2 and first.shape[1] == LANES, (
            "plane stage operands must be (rows, LANES) layout buffers",
            first.shape,
        )
        out_dtypes = {
            n: (likes[i].dtype if n == "x" else jnp.float32) for n in names_out
        }
        row_scalars = None
        if r_cols is not None:
            row_scalars = {
                "r": jnp.broadcast_to(
                    r_cols[i].astype(jnp.float32), (rows, ROW_COLS)
                )
            }
        res = fused_stage_kernel(
            kind, op, ctx, svec, leaf_ins, out_dtypes,
            block_rows=64, interpret=interpret, row_scalars=row_scalars,
        )
        for name in names_out:
            out_cols[name].append(res[name])
    return {n: jax.tree.unflatten(treedef, col) for n, col in out_cols.items()}


def make_plane_stage(impl: str = "pallas", *, interpret: bool = False):
    """Stage executor for ``run_update`` over plane-packed operands.

    ``ref`` returns :func:`~repro.core.update_spec.reference_stage` — the
    stage math broadcasts the row-indexed LARS columns exactly like any
    other operand, so the pure-jnp oracle runs on planes unchanged (this is
    what the plane-vs-per-leaf parity tests pin).  ``pallas`` /
    ``pallas_interpret`` return the whole-plane kernel executor.
    """
    if impl == "ref":
        return reference_stage
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown fused impl {impl!r}")
    return functools.partial(
        fused_plane_stage, interpret=interpret or impl == "pallas_interpret"
    )


@functools.partial(jax.jit, static_argnames=("beta", "impl", "interpret"))
def decentlam_update(
    params,
    mixed,
    momentum,
    lr,
    *,
    beta: float,
    impl: str = "ref",  # ref | pallas | pallas_interpret
    interpret: bool = False,
):
    """Fused DecentLaM tail (eq. 17 + momentum + step) over a pytree.

    Given pre-gossiped ``mixed = G(x - lr * g)``::

        g~    = (x - mixed) / lr
        m_new = beta * m + g~
        x_new = x - lr * m_new

    Returns ``(new_params, new_momentum)``.  The unfused form touches HBM
    ~9x per element; the fused stage reads (x, mixed, m) and writes
    (x_new, m_new) in one pass.
    """
    ctx = MathCtx(beta=beta)
    scalars = {
        "lr": jnp.asarray(lr, jnp.float32).reshape(()),
        "gs": jnp.float32(1.0),
        "r": jnp.float32(1.0),
    }
    stage = make_stage(impl, interpret=interpret)
    out = stage(
        "post",
        "decentlam_post",
        ctx,
        {"x": params, "mix": mixed, "m": momentum},
        scalars,
        params,
    )
    return out["x"], out["m"]
