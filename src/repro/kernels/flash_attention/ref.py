"""Pure-jnp oracle for the flash-attention kernel.

Materializes the full (Sq, Sk) score matrix in fp32 — only usable at test
shapes, which is the point: it is the ground truth the Pallas kernel is
checked against (causal x window x GQA x dtype sweeps).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def reference_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,  # (B, Sk, Hkv, hd)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    assert H % Hkv == 0
    g = H // Hkv
    kf = jnp.repeat(k, g, axis=2)
    vf = jnp.repeat(v, g, axis=2)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    # f32 matmuls run in bf16 passes on a TPU unless asked for full precision
    highest = jax.lax.Precision.HIGHEST
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), kf.astype(jnp.float32),
        precision=highest,
    ) * scale
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p, vf.astype(jnp.float32), precision=highest
    )
    return out.astype(q.dtype)
