"""Grouped matrix products over ragged row groups: Pallas TPU kernels.

The rows of ``lhs`` come sorted by group, ``group_sizes[g]`` of them for
group ``g``, in order.  Two kernels:

* ``gmm``:  ``out[rows of g] = lhs[rows of g] @ rhs[g]`` (``rhs[g].T`` with
  ``transpose_rhs``), an ``(m, n)`` result;
* ``tgmm``: ``out[g] = lhs[rows of g].T @ rhs[rows of g]``, one ``(k, n)``
  block per group, zero for an empty group.

Both walk the row tiles group by group, from the group metadata of the
installed megablox kernels (``make_group_metadata``): a tile that two groups
share is visited once for each, and each visit stores (``gmm``) or reads
(``tgmm``) only its group's rows, so no row is computed into a group it is
not in.  Inputs are bf16 (f32 in the tests) and every product accumulates
in an f32 VMEM tile.  ``group_offset`` (a traced scalar) and a ``rhs`` (or,
for ``tgmm``, ``num_groups``) short of all the groups select the groups this
device holds, as expert parallelism needs; rows of other groups come out 0.

The grid's middle axis is the number of row tiles the groups need, a
traced count.  Adapted from ``jax.experimental.pallas.ops.tpu.megablox``
(Apache 2.0): every ``pallas_call`` here carries a ``name`` that the
compiled program gives its instructions, so a device trace finds it.
``interpret=True`` runs the same kernel body on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

__all__ = ["gmm", "tgmm"]


def _row_mask(group_metadata, grid_id, tm: int, width: int):
    """(tm, width) mask of the tile's rows that belong to its group."""
    offsets, group_ids, m_tile_ids = group_metadata
    g = group_ids[grid_id]
    row = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + m_tile_ids[grid_id] * tm
    return (row >= offsets[g]) & (row < offsets[g + 1])


def promote(*arrays):
    """Inside a shard_map that checks varying axes the kernel's result must
    declare them: every operand is promoted to the union of theirs."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in arrays))
    out = []
    for a in arrays:
        missing = tuple(sorted(vma - jax.typeof(a).vma))
        out.append(lax.pcast(a, missing, to="varying") if missing else a)
    return vma, out


def gmm(lhs, rhs, group_sizes, group_offset, *, tiling, transpose_rhs: bool,
        out_dtype, name: str, interpret: bool):
    """``lhs (m, k)`` by ``rhs (groups here, k, n)`` (or ``(.., n, k)``)."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (lhs.shape, rhs.shape, tiling)
    tiles_k = k // tk
    local = rhs.shape[0]
    metadata, num_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=group_offset,
        num_nonzero_groups=local, visit_empty_groups=False)

    def kernel(metadata, group_offset, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))
        acc_ref[...] += lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                        preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _():
            mask = _row_mask(metadata, grid_id, tm, tn)
            out_ref[...] = lax.select(mask, acc_ref[...],
                                      out_ref[...].astype(jnp.float32)).astype(out_dtype)

    def lhs_map(n_i, grid_id, k_i, metadata, group_offset):
        return metadata[2][grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, metadata, group_offset):
        g = metadata[1][grid_id] - group_offset[0]
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, grid_id, k_i, metadata, group_offset):
        return metadata[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    group_offset = group_offset.reshape(1)
    vma, (lhs, rhs, *metadata, group_offset) = promote(lhs, rhs, *metadata, group_offset)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * lhs.dtype.itemsize * (n // tn)
                            + local * k * n * rhs.dtype.itemsize
                            + m * n * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
        name=name,
    )(tuple(metadata), group_offset, lhs, rhs)
    if local < group_sizes.shape[0]:
        # rows of the groups another device holds were never stored
        offsets = metadata[0]
        row = lax.broadcasted_iota(jnp.int32, (m, 1), 0)
        held = (row >= offsets[group_offset[0]]) & (row < offsets[group_offset[0] + local])
        out = jnp.where(held, out, jnp.zeros((), out.dtype))
    return out


def tgmm(lhs, rhs, group_sizes, group_offset, *, num_groups: int, tiling,
         out_dtype, name: str, interpret: bool):
    """``lhs (m, k)`` and ``rhs (m, n)`` -> ``(num_groups, k, n)``, the
    groups from ``group_offset`` on."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tm, tk, tn = tiling
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (lhs.shape, rhs.shape, tiling)
    metadata, num_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=group_offset,
        num_nonzero_groups=num_groups, visit_empty_groups=True)

    def kernel(metadata, group_offset, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id = pl.program_id(2)
        offsets, group_ids, _ = metadata
        g = group_ids[grid_id]
        first = (grid_id == 0) | (group_ids[jnp.maximum(grid_id - 1, 0)] != g)
        last = ((grid_id == pl.num_programs(2) - 1)
                | (group_ids[jnp.minimum(grid_id + 1, pl.num_programs(2) - 1)] != g))

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[g + 1] > offsets[g])
        def _():
            # rows of the tile outside the group are zeroed (in f32: the
            # v5e's vector unit has no bf16 select), then the tile's lhs is
            # transposed for the MXU
            a = jnp.where(_row_mask(metadata, grid_id, tm, tk),
                          lhs_ref[...].astype(jnp.float32), 0.0)
            b = jnp.where(_row_mask(metadata, grid_id, tm, tn),
                          rhs_ref[...].astype(jnp.float32), 0.0)
            acc_ref[...] += lax.dot(a.swapaxes(0, 1).astype(lhs_ref.dtype),
                                    b.astype(rhs_ref.dtype),
                                    preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_dtype)

    def lhs_map(n_i, k_i, grid_id, metadata, group_offset):
        return metadata[2][grid_id], k_i

    def rhs_map(n_i, k_i, grid_id, metadata, group_offset):
        return metadata[2][grid_id], n_i

    def out_map(n_i, k_i, grid_id, metadata, group_offset):
        return metadata[1][grid_id] - group_offset[0], k_i, n_i

    group_offset = group_offset.reshape(1)
    vma, (lhs, rhs, *metadata, group_offset) = promote(lhs, rhs, *metadata, group_offset)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * lhs.dtype.itemsize * (n // tn)
                            + m * n * rhs.dtype.itemsize * (k // tk)
                            + num_groups * k * n * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret,
        name=name,
    )(tuple(metadata), group_offset, lhs, rhs)
