"""``moe_gmm``: the grouped product of the expert layer, differentiable.

    out = moe_gmm(lhs, rhs, group_sizes, group_offset)

``lhs`` is ``(m, k)`` with its rows sorted by group, ``rhs`` the
``(groups here, k, n)`` weights, ``group_sizes`` the rows of every group
(all of them, also those another device holds) and ``group_offset`` the
first group held here.  Its ``custom_vjp`` computes ``dlhs`` by the same
kernel against the transposed weights and ``drhs`` by the transposed
grouped product, so the three calls of a product are named
``moe_gmm_fwd``, ``moe_gmm_dlhs`` and ``moe_gmm_drhs``.

Rows are padded up to a whole tile for the kernel and cut off after it.
On a TPU the kernels lower through Mosaic; on any other platform the same
kernel bodies run in Pallas interpret mode (off a TPU host,
``lax.platform_dependent`` decides as the program is lowered, so a program
compiled for a TPU from another host holds the TPU kernels).  Inside a shard_map, interpret mode
runs every member's call on the gathered operands (``_interpret``): only
the CPU's program pays for that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import kernel

__all__ = ["moe_gmm", "sum_to", "tiling"]

TILE_M = 512  # rows of a tile; see PERF.md for the sizes timed on the v5e


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(rows, k, n) of a tile: whole k and n (the registry's expert layers
    have ``d_model`` at most 1536 and ``d_ff`` 512), ``TILE_M`` rows or all
    of a smaller ``m`` rounded up to 16 (the bf16 sublane tile)."""
    return min(TILE_M, -(-m // 16) * 16), k, n


def _pad_rows(x, m_pad: int):
    return x if x.shape[0] == m_pad else jnp.pad(x, ((0, m_pad - x.shape[0]), (0, 0)))


def _interpret(fn, *args, **kw):
    """``fn`` in Pallas interpret mode.  The interpreter's own grid indices
    vary over no mesh axis, and it indexes the operands with them, so inside
    a shard_map the operands are first gathered over the axes they vary
    over; each member's call then runs in turn, and this member's result is
    taken."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in args))
    if not vma:
        return fn(*args, interpret=True, **kw)
    axes = tuple(sorted(vma))
    me, members = lax.axis_index(axes), lax.axis_size(axes)
    _, args = kernel.promote(*args)
    # a psum of each member's operands in its own slot: invariant copies
    every = [lax.psum(jnp.zeros((members,) + a.shape, a.dtype).at[me].set(a), axes)
             for a in args]
    outs = lax.map(lambda a: fn(*a, interpret=True, **kw), every)
    return outs[me]


def _on_platform(fn, *args, **kw):
    tpu = functools.partial(fn, interpret=False, **kw)
    if jax.default_backend() == "tpu":  # trace one branch: less set-up
        return tpu(*args)
    return lax.platform_dependent(
        *args, tpu=tpu, default=functools.partial(_interpret, fn, **kw))


def _gmm(lhs, rhs, group_sizes, group_offset, *, transpose_rhs: bool, name: str):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiles = tiling(m, k, n)
    m_pad = -(-m // tiles[0]) * tiles[0]
    out = _on_platform(
        kernel.gmm, _pad_rows(lhs, m_pad), rhs, group_sizes, group_offset,
        tiling=tiles, transpose_rhs=transpose_rhs, out_dtype=lhs.dtype, name=name)
    return out[:m]


def _tgmm(lhs, rhs, group_sizes, group_offset, *, num_groups: int, out_dtype):
    m, k = lhs.shape
    tiles = tiling(m, k, rhs.shape[1])
    m_pad = -(-m // tiles[0]) * tiles[0]
    return _on_platform(
        kernel.tgmm, _pad_rows(lhs, m_pad), _pad_rows(rhs, m_pad), group_sizes,
        group_offset, num_groups=num_groups, tiling=tiles, out_dtype=out_dtype,
        name="moe_gmm_drhs")


@jax.custom_vjp
def _moe_gmm(lhs, rhs, group_sizes, group_offset):
    return _gmm(lhs, rhs, group_sizes, group_offset, transpose_rhs=False,
                name="moe_gmm_fwd")


def _moe_gmm_fwd(lhs, rhs, group_sizes, group_offset):
    out = _moe_gmm(lhs, rhs, group_sizes, group_offset)
    return out, (lhs, rhs, group_sizes, group_offset)


def sum_to(ct, like):
    """A cotangent typed as its primal ``like``: summed over the mesh axes
    it varies over and ``like`` does not (the transpose of the implicit
    broadcast that met ``like`` with a varying operand)."""
    extra = tuple(sorted(jax.typeof(ct).vma - jax.typeof(like).vma))
    return lax.psum(ct, extra) if extra else ct


def _moe_gmm_bwd(res, dout):
    lhs, rhs, group_sizes, group_offset = res
    dlhs = _gmm(dout, rhs, group_sizes, group_offset, transpose_rhs=True,
                name="moe_gmm_dlhs")
    drhs = _tgmm(lhs, dout, group_sizes, group_offset, num_groups=rhs.shape[0],
                 out_dtype=rhs.dtype)
    return sum_to(dlhs, lhs), sum_to(drhs, rhs), None, None


_moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)


def moe_gmm(lhs, rhs, group_sizes, group_offset=None):
    """``out[rows of g] = lhs[rows of g] @ rhs[g - group_offset]``; rows of
    groups not held here come out 0."""
    if group_offset is None:
        group_offset = jnp.zeros((), jnp.int32)
    return _moe_gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                    jnp.asarray(group_offset, jnp.int32))
