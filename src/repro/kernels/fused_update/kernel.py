"""Generic fused optimizer-stage Pallas TPU kernel.

One kernel family covers every elementwise stage of every algorithm's update
tail (see ``repro.core.update_spec``): the stage op is a compile-time enum,
so each (kind, op, MathCtx) pair lowers to its own fully-fused elementwise
kernel — one read of the operands, one write of the outputs, per leaf.

Tensors are flattened and tiled (rows, 1024) with (block_rows, 1024) VMEM
blocks — lane-dim 1024 = 8 x 128 keeps the VPU fully fed.  The traced
scalars (lr, clip scale, LARS trust ratio, staleness damping, and under the
finite guard its flag) arrive as a single (4,) or (5,) f32 vector in SMEM;
all other constants (beta, weight decay, nesterov, the guard, the op
itself) are baked into the kernel.

The kernel body calls the *same* ``pre_math``/``post_math`` the pure-JAX
reference path uses, so parity with the stacked oracle holds by
construction; ``interpret=True`` runs the identical math on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.update_spec import MathCtx, post_math, pre_math

LANES = 1024
ROW_COLS = 128  # lane width of a row-scalar operand (one VMEM tile column)


def _stage_body(
    s_ref, *refs, kind: str, op: str, ctx: MathCtx, names_in, names_out, names_row=()
):
    nrow = len(names_row)
    rows, ins = refs[:nrow], refs[nrow: nrow + len(names_in)]
    outs = refs[nrow + len(names_in):]
    s = {"lr": s_ref[0], "gs": s_ref[1], "r": s_ref[2], "sg": s_ref[3]}
    if ctx.guard:
        s["ok"] = s_ref[4]
    # row-indexed segment scalars (plane layout): a (block_rows, 1) column
    # overrides the SMEM scalar and broadcasts across the lanes, giving each
    # leaf's rows their own value inside the single whole-plane launch
    for n, rref in zip(names_row, rows):
        s[n] = rref[...][:, :1].astype(jnp.float32)
    vals = {n: r[...].astype(jnp.float32) for n, r in zip(names_in, ins)}
    math = pre_math if kind == "pre" else post_math
    res = math(op, ctx, s, **vals)
    for n, oref in zip(names_out, outs):
        oref[...] = res[n].astype(oref.dtype)


def fused_stage_kernel(
    kind: str,
    op: str,
    ctx: MathCtx,
    scalars: jax.Array,  # (4,) f32 in SMEM: lr, clip scale, LARS ratio, sg
    # (+ the guard's ok, 1.0 or 0.0, when ctx.guard)
    inputs: dict[str, jax.Array],  # each (rows, LANES)
    out_dtypes: dict[str, jnp.dtype],
    *,
    block_rows: int = 64,
    interpret: bool = False,
    row_scalars: dict[str, jax.Array] | None = None,  # each (rows, ROW_COLS)
):
    """One fused elementwise stage over pre-tiled operands.

    ``row_scalars`` carries per-row overrides of the SMEM stage scalars
    (the plane layout's row-indexed segment scalars, e.g. the per-leaf
    LARS trust ratio ``r``) as narrow ``(rows, ROW_COLS)`` f32 operands —
    one VMEM tile column, ~1/8 of an operand's bandwidth, only present
    when the feature needs it.
    """
    names_in = tuple(inputs)
    names_out = tuple(out_dtypes)
    row_scalars = row_scalars or {}
    names_row = tuple(row_scalars)
    first = inputs[names_in[0]]
    rows = first.shape[0]
    # blocks need not divide the rows: Pallas masks the boundary block
    # (plane buffers carry no tail padding; the per-leaf path still
    # pre-pads each leaf so its grid is exact)
    grid = (-(-rows // block_rows),)
    bs = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    bs_row = pl.BlockSpec((block_rows, ROW_COLS), lambda i: (i, 0))

    # inside a check_vma shard_map the outputs must declare their
    # varying axes; they inherit the inputs' (elementwise kernel), and every
    # operand must be promoted to the same variance (scalars are replicated)
    vma = frozenset().union(*(jax.typeof(a).vma for a in inputs.values()))
    if vma:

        def _promote(a):
            missing = tuple(sorted(vma - jax.typeof(a).vma))
            return jax.lax.pcast(a, missing, to="varying") if missing else a

        scalars = _promote(scalars)
        inputs = {n: _promote(a) for n, a in inputs.items()}
        row_scalars = {n: _promote(a) for n, a in row_scalars.items()}

    out_shape = [
        jax.ShapeDtypeStruct(first.shape, dt, vma=vma) for dt in out_dtypes.values()
    ]

    # under the guard a state output is the step's final state, restored
    # from its own input (update_spec._keep): write it in place, so the
    # donated state buffer takes it with no copy
    aliases = {}
    if ctx.guard:
        for j, n in enumerate(names_out):
            if n not in ("x", "payload") and n in names_in:
                aliases[1 + len(names_row) + names_in.index(n)] = j

    kern = functools.partial(
        _stage_body, kind=kind, op=op, ctx=ctx, names_in=names_in,
        names_out=names_out, names_row=names_row,
    )
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [bs_row] * len(names_row)
        + [bs] * len(names_in),
        out_specs=[bs] * len(names_out),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        # a stable name for the kernel in traces and in the compiled HLO
        name=f"fused_update_{kind}_{op}",
    )(scalars, *row_scalars.values(), *inputs.values())
    return dict(zip(names_out, outs))
