"""Plain jnp oracles of the grouped products, one group at a time.

Row ``r`` of ``lhs`` belongs to group ``g`` when
``offsets[g] <= r < offsets[g + 1]``, with ``offsets`` the running sum of
``group_sizes``; rows past every group, and rows of groups before
``group_offset`` or past the ones given, come out 0.
"""

from __future__ import annotations

import jax.numpy as jnp


def _group_of_rows(group_sizes, m: int):
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(m), side="right")


def gmm_ref(lhs, rhs, group_sizes, group_offset=0, *, transpose_rhs=False):
    """``out[rows of g] = lhs[rows of g] @ rhs[g - group_offset]``."""
    w = rhs.swapaxes(1, 2) if transpose_rhs else rhs
    g = _group_of_rows(group_sizes, lhs.shape[0]) - group_offset
    held = (g >= 0) & (g < w.shape[0])
    out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32),
                     w[jnp.clip(g, 0, w.shape[0] - 1)].astype(jnp.float32),
                     precision="highest")
    return jnp.where(held[:, None], out, 0.0)


def tgmm_ref(lhs, rhs, group_sizes, group_offset=0, *, num_groups=None):
    """``out[g - group_offset] = lhs[rows of g].T @ rhs[rows of g]``."""
    num_groups = group_sizes.shape[0] if num_groups is None else num_groups
    g = _group_of_rows(group_sizes, lhs.shape[0]) - group_offset
    onehot = (g[:, None] == jnp.arange(num_groups)[None]).astype(jnp.float32)
    return jnp.einsum("mg,mk,mn->gkn", onehot, lhs.astype(jnp.float32),
                      rhs.astype(jnp.float32), precision="highest")
