"""Attention with manual tensor parallelism.

Sharding scheme (DESIGN.md §4):

* **train / prefill** — q heads are column-sharded over the model axis
  (heads padded up to a multiple of tp; padded heads are masked so they
  neither contribute outputs nor receive gradients).  K/V are sharded over
  kv-heads when divisible, otherwise computed replicated (GQA kv-heads are
  small).  The jnp core loops over at most eight spans of q blocks, each
  scored only against the keys it can see (the causal prefix, cut on the
  left by a static window), so masked-out key blocks are never computed;
  the blocks within a span run one after another.  In a rematerialised layer
  each block is scored twice a step (forward and the layer's recompute);
  only where one layer's probabilities are too large to save does each
  block carry its own checkpoint, and a third scoring.
  The out-projection is row-sharded -> one psum.
* **decode** — the KV cache is *sequence-sharded* over the model axis
  (split-K / flash-decoding): the new token's q is all-gathered (tiny), every
  device scores its own cache chunk, and partial (max, sum-exp, weighted-V)
  stats merge with pmax/psum.  This works for any kv-head count — the
  TPU-shaped answer to "kv heads don't divide the axis".
* **sliding window** — a rolling buffer of ``window`` slots (also
  seq-sharded) with explicit per-slot positions; gives O(window) decode for
  SWA archs (h2o-danube, hymba) and enables the long_500k cells.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .layers import Initializer, TPContext, apply_rope, linear_init, rms_norm

Tree = Any

__all__ = [
    "AttnDims",
    "attn_init",
    "attn_specs",
    "attn_forward",
    "init_kv_cache",
    "kv_cache_specs",
    "attn_decode_step",
    "attention_core",
]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int  # real q heads
    n_heads_padded: int
    n_kv: int
    hd: int
    tp: int
    kv_sharded: bool

    @classmethod
    def resolve(cls, cfg: ModelConfig, tp: int, serve: bool = False) -> "AttnDims":
        hp = cfg.n_heads_padded(tp)
        # serve paths keep full kv heads on every shard (the cache is
        # sequence-sharded instead), so kv projections stay replicated there.
        kv_sharded = (
            (cfg.n_kv_heads % tp == 0) and (cfg.n_heads % tp == 0) and not serve
        )
        return cls(
            n_heads=cfg.n_heads,
            n_heads_padded=hp,
            n_kv=cfg.n_kv_heads,
            hd=cfg.hd,
            tp=tp,
            kv_sharded=kv_sharded,
        )

    @property
    def h_local(self) -> int:
        return self.n_heads_padded // self.tp

    @property
    def kv_local(self) -> int:
        return self.n_kv // self.tp if self.kv_sharded else self.n_kv


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_init(init: Initializer, cfg: ModelConfig, tp: int) -> Tree:
    d, hd = cfg.d_model, cfg.hd
    dims = AttnDims.resolve(cfg, tp)
    p = {
        "wq": linear_init(init, d, dims.n_heads_padded * hd),
        "wk": linear_init(init, d, dims.n_kv * hd),
        "wv": linear_init(init, d, dims.n_kv * hd),
        "wo": linear_init(init, dims.n_heads_padded * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = init.zeros((hd,))
        p["k_norm"] = init.zeros((hd,))
    return p


def attn_specs(
    cfg: ModelConfig, tp: int, model_axis: str = "model", serve: bool = False
) -> Tree:
    dims = AttnDims.resolve(cfg, tp, serve=serve)
    kv = P(None, model_axis) if dims.kv_sharded else P(None, None)
    p = {
        "wq": P(None, model_axis),
        "wk": kv,
        "wv": kv,
        "wo": P(model_axis, None),
    }
    if cfg.qk_norm:
        p["q_norm"] = P(None)
        p["k_norm"] = P(None)
    return p


def _head_mask(dims: AttnDims, tp_ctx: TPContext) -> jax.Array:
    """(h_local,) 1.0 for real heads, 0.0 for padding heads on this shard."""
    base = tp_ctx.axis_index() * dims.h_local
    idx = base + jnp.arange(dims.h_local)
    return (idx < dims.n_heads).astype(jnp.float32)


def _group_index(dims: AttnDims, tp_ctx: TPContext) -> jax.Array:
    """(h_local,) kv-group id (into the *local* kv tensor) per local q head."""
    q_per_kv = max(dims.n_heads // dims.n_kv, 1)
    base = tp_ctx.axis_index() * dims.h_local
    g = jnp.clip((base + jnp.arange(dims.h_local)) // q_per_kv, 0, dims.n_kv - 1)
    if dims.kv_sharded:
        g = g - tp_ctx.axis_index() * dims.kv_local
    return g


# ---------------------------------------------------------------------------
# Core attention (q blocks over the key span each can see)
# ---------------------------------------------------------------------------

# A layer's saved probabilities (f32 softmax output and its cast to the value
# dtype) under this many bytes are kept for the backward pass; above it each
# q block is checkpointed and scored again there.
PROBS_BUDGET_BYTES = 2**30


def default_q_block(Sq: int) -> int:
    """Rows per q block for ``Sq`` queries: an eighth of them, rounded up to
    whole tiles of 128 rows, and at most 512, so one block's scores are at
    most 512 rows by ``Sk``.  At 2048 that is 256, which a TPU v5e ran faster
    than 512: the causal core then scores 0.5625 of the score area, not
    0.625."""
    return min(128 * max(1, -(-Sq // 1024)), 512)


def _block_spans(Sq: int, Sk: int, bq: int, causal: bool, window: int):
    """``(q0, q1, lo, hi)`` per span of rows: rows ``[q0, q1)`` score keys
    ``[lo, hi)``, the keys any of its rows can see.  The rows are cut into at
    most eight spans of whole ``bq`` blocks, and neighbouring spans that see
    the same keys are joined.  Causal: the key prefix up to the span's last
    row; a static ``window > 0`` also drops the key blocks left of the first
    key in any row's window."""
    nb = -(-Sq // bq)
    sb = bq * -(-nb // 8)
    spans = []
    for q0 in range(0, Sq, sb):
        q1 = min(q0 + sb, Sq)
        hi = min(q1, Sk) if causal else Sk
        lo = max(q0 - window + 1, 0) // bq * bq if window > 0 else 0
        if lo >= hi:  # no row sees a key: mask the full span, as a full row
            lo, hi = 0, Sk
        if spans and spans[-1][2:] == (lo, hi):
            spans[-1] = (spans[-1][0], q1, lo, hi)
        else:
            spans.append((q0, q1, lo, hi))
    return spans


def score_block_share(Sq: int, Sk: int, bq: int, causal: bool, window: int = 0) -> float:
    """Share of the ``Sq x Sk`` score area that the block core computes."""
    area = sum((q1 - q0) * (hi - lo) for q0, q1, lo, hi in
               _block_spans(Sq, Sk, bq, causal, window))
    return area / (Sq * Sk)


def _score_block(qb, kb, vb, *, q0, lo, causal, window, softcap):
    """Rows ``q0 + [0, bq)`` of q against keys ``lo + [0, bk)``; a ``window``
    > 0 masks keys at or past that distance.  ``q0`` is an int, or traced
    where the blocks of a span are mapped: the mask is then always built."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(qb.shape[-1], jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    bq, bk = qb.shape[1], kb.shape[1]
    q_pos = q0 + jnp.arange(bq)[:, None]
    k_pos = lo + jnp.arange(bk)[None, :]
    static = isinstance(q0, int)
    mask = None
    # masks only where some row of the block cannot see some key of its span
    if causal and (not static or lo + bk - 1 > q0):
        mask = k_pos <= q_pos
    if window > 0 and (not static or q0 + bq - 1 - lo >= window):
        m = q_pos - k_pos < window
        mask = m if mask is None else mask & m
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb)


@jax.named_scope("attn_core")
def attention_core(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: int = 0,
    softcap: float = 0.0,
    q_block: int | None = None,
    impl: str = "jnp",
    remat: bool = True,
) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Sk, Hkv_grouped-to-H, hd) — kv already
    expanded to H heads.  Returns (B, Sq, H, hd).

    The jnp core is a static loop over at most eight row spans, each scored
    against the keys it can see (``_block_spans``), in q blocks of
    ``default_q_block`` rows; the whole blocks of a span that holds more than
    one run one after another under ``lax.map``.  The blocks' outputs are
    joined by one ``concatenate``.  Scores and softmax are f32; ``p`` is cast
    to the value dtype for the PV product.  ``remat`` says the enclosing
    layer is rematerialised: its recompute then saves what the backward pass
    needs, unless one layer's saved probabilities pass ``PROBS_BUDGET_BYTES``,
    where each block carries its own checkpoint and is scored once more in
    the backward pass."""
    if impl in ("pallas", "pallas_interpret"):
        from ..kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(
            q, k, v, causal=causal, window=window,
            interpret=(impl == "pallas_interpret"),
        )
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    bq = min(q_block or default_q_block(Sq), Sq)
    probs_bytes = (B * H * Sq * Sk * score_block_share(Sq, Sk, bq, causal, window)
                   * (4 + jnp.dtype(v.dtype).itemsize))
    checkpoint = remat and probs_bytes > PROBS_BUDGET_BYTES

    def attend(qb, kb, vb, q0, lo):
        block = functools.partial(_score_block, q0=q0, lo=lo, causal=causal,
                                  window=window, softcap=softcap)
        return (jax.checkpoint(block) if checkpoint else block)(qb, kb, vb)

    outs = []
    for q0, q1, lo, hi in _block_spans(Sq, Sk, bq, causal, window):
        kb, vb = k[:, lo:hi], v[:, lo:hi]
        n = (q1 - q0) // bq
        if n > 1:
            qs = q[:, q0:q0 + n * bq].reshape(B, n, bq, H, hd).swapaxes(0, 1)
            out = jax.lax.map(lambda a: attend(a[0], kb, vb, a[1], lo),
                              (qs, q0 + bq * jnp.arange(n)))
            outs.append(out.swapaxes(0, 1).reshape(B, n * bq, H, hd))
            q0 += n * bq
        for r in range(q0, q1, bq):  # a span of one block, and a ragged tail
            outs.append(attend(q[:, r:min(r + bq, q1)], kb, vb, r, lo))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _expand_kv(k: jax.Array, dims: AttnDims, tp_ctx: TPContext) -> jax.Array:
    """(B, S, KVloc, hd) -> (B, S, h_local, hd) via the GQA group map."""
    g = _group_index(dims, tp_ctx)
    return jnp.take(k, g, axis=2)


# ---------------------------------------------------------------------------
# Train / prefill forward
# ---------------------------------------------------------------------------


def attn_forward(
    x: jax.Array,
    params: Tree,
    cfg: ModelConfig,
    tp_ctx: TPContext,
    *,
    positions: jax.Array | None = None,
    causal: bool = True,
    window: int = 0,
    attn_impl: str = "jnp",
    remat: bool = True,
    return_kv: bool = False,
    serve: bool = False,
    kv_source: jax.Array | None = None,
):
    """x: (B, S, d) replicated over model axis -> (B, S, d) replicated.

    ``window`` is static: 0 is full attention, > 0 a sliding window.
    ``kv_source`` switches to cross-attention: k/v computed from it.
    """
    B, S, d = x.shape
    dims = AttnDims.resolve(cfg, tp_ctx.size, serve=serve)
    dt = x.dtype
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    src = x if kv_source is None else kv_source.astype(dt)
    Sk = src.shape[1]

    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", src, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", src, params["wv"].astype(dt))
    q = q.reshape(B, S, dims.h_local, dims.hd)
    k = k.reshape(B, Sk, dims.kv_local, dims.hd)
    v = v.reshape(B, Sk, dims.kv_local, dims.hd)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_source is None:
            k = apply_rope(k, positions, cfg.rope_theta)

    kf = _expand_kv(k, dims, tp_ctx)
    vf = _expand_kv(v, dims, tp_ctx)

    out = attention_core(
        q, kf, vf, causal=causal, window=window, impl=attn_impl, remat=remat,
        softcap=cfg.logit_softcap,
    )

    out = out * _head_mask(dims, tp_ctx)[None, None, :, None].astype(dt)
    out = out.reshape(B, S, dims.h_local * dims.hd)
    from jax.ad_checkpoint import checkpoint_name

    y = checkpoint_name(
        tp_ctx.psum(jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dt))),
        "tp_psum",
    )
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# Decode: sequence-sharded KV cache with split-K merge
# ---------------------------------------------------------------------------


def init_kv_cache(
    cfg: ModelConfig,
    n_layers: int,
    batch: int,
    capacity: int,
    tp: int,
    dtype=jnp.bfloat16,
) -> Tree:
    """Cache pytree (leaves carry a leading layer axis for scan).

    ``capacity`` is the *global* number of slots; each model shard stores
    ``capacity / tp`` contiguous slots.  ``pos`` tracks each slot's absolute
    position (-1 = empty) so rolling windows and masking are explicit.
    """
    dims = AttnDims.resolve(cfg, tp)
    assert capacity % tp == 0, f"cache capacity {capacity} % tp {tp}"
    s_local = capacity // tp
    return {
        "k": jnp.zeros((n_layers, batch, s_local, dims.n_kv, dims.hd), dtype),
        "v": jnp.zeros((n_layers, batch, s_local, dims.n_kv, dims.hd), dtype),
        "pos": jnp.full((n_layers, batch, s_local), -1, jnp.int32),
    }


def kv_cache_specs(batch_axes, model_axis: str = "model") -> Tree:
    """Cache sharding: batch over node axes, slots over model axis."""
    return {
        "k": P(None, batch_axes, model_axis, None, None),
        "v": P(None, batch_axes, model_axis, None, None),
        "pos": P(None, batch_axes, model_axis),
    }


def attn_decode_step(
    x: jax.Array,
    params: Tree,
    cache_layer: Tree,
    cfg: ModelConfig,
    tp_ctx: TPContext,
    *,
    t: jax.Array,  # absolute position of the new token, (B,) or scalar
    window: int | jax.Array = 0,
    capacity: int = 0,  # global slot count (static)
    grouped: bool = False,  # grouped-GQA scores (no KV head expansion)
):
    """One-token decode with a sequence-sharded cache.

    x: (B, 1, d) replicated over model.  Returns (y, new_cache_layer).
    Write slot: ``t % capacity`` (rolling when window > 0 sized capacity).
    """
    B, S1, d = x.shape
    assert S1 == 1
    dims = AttnDims.resolve(cfg, tp_ctx.size, serve=True)
    dt = x.dtype
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))

    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"].astype(dt))
    q = q.reshape(B, 1, dims.h_local, dims.hd)
    k = k.reshape(B, 1, dims.n_kv, dims.hd)
    v = v.reshape(B, 1, dims.n_kv, dims.hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if cfg.rope_theta > 0:
        q = apply_rope(q, t[:, None], cfg.rope_theta)
        k = apply_rope(k, t[:, None], cfg.rope_theta)

    # ---- all-gather q across model so every shard sees all heads (tiny) ----
    if tp_ctx.enabled:
        qf = jax.lax.all_gather(q, tp_ctx.axis, axis=2, tiled=True)
        qf = qf[:, :, : dims.n_heads_padded]  # (B, 1, Hp, hd)
    else:
        qf = q
    # mask padded heads in q so their (uniform) outputs vanish after merge
    hp_mask = (jnp.arange(dims.n_heads_padded) < dims.n_heads).astype(jnp.float32)

    # ---- write new kv into this shard's slot if it owns position t ----
    s_local = cache_layer["k"].shape[1]  # cache_layer["k"]: (B, s_local, KV, hd)
    cap = capacity if capacity else s_local * tp_ctx.size
    slot = t % cap
    owner = slot // s_local
    local_slot = slot - owner * s_local
    me = tp_ctx.axis_index()

    def write(buf, new):
        # buf: (B, s_local, KV, hd); new: (B, 1, KV, hd)
        idx = jnp.clip(local_slot, 0, s_local - 1)
        upd = jax.vmap(lambda b, n, i: jax.lax.dynamic_update_slice(b, n, (i, 0, 0)))(
            buf, new.astype(buf.dtype), idx
        )
        keep = (owner == me)[:, None, None, None]
        return jnp.where(keep, upd, buf)

    new_k = write(cache_layer["k"], k)
    new_v = write(cache_layer["v"], v)
    pos_upd = jax.vmap(
        lambda p, i, tt: jax.lax.dynamic_update_slice(p, tt[None], (i,))
    )(cache_layer["pos"], jnp.clip(local_slot, 0, s_local - 1), t)
    new_pos = jnp.where((owner == me)[:, None], pos_upd, cache_layer["pos"])

    # ---- split-K attention over the local chunk ----
    valid = new_pos >= 0
    valid &= new_pos <= t[:, None]
    w = jnp.asarray(window, jnp.int32)
    valid &= jnp.where(w > 0, t[:, None] - new_pos < w, True)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dims.hd, jnp.float32))

    can_group = (
        grouped
        and dims.n_heads == dims.n_heads_padded
        and dims.n_heads % dims.n_kv == 0
    )
    if can_group:
        # grouped-GQA scores: contract q-head groups against the raw KV
        # cache directly — never materializes the (Hp-expanded) K/V copies
        gp = dims.n_heads // dims.n_kv
        qg = qf.reshape(B, 1, dims.n_kv, gp, dims.hd)
        s = jnp.einsum("bqegd,bked->begqk", qg, new_k).astype(jnp.float32)
        s = s * scale  # (B, KV, gp, 1, s_local)
        if cfg.logit_softcap > 0.0:
            s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        s = s.reshape(B, dims.n_heads_padded, 1, -1)
    else:
        kv_g = _group_full(new_k, dims)  # (B, s_local, Hp, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kv_g).astype(jnp.float32) * scale
        if cfg.logit_softcap > 0.0:
            s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)

    m_loc = jnp.max(s, axis=-1)  # (B, Hp, 1)
    if tp_ctx.enabled:
        m = jax.lax.pmax(m_loc, tp_ctx.axis)
    else:
        m = m_loc
    p = jnp.exp(s - m[..., None])
    l_loc = jnp.sum(p, axis=-1)  # (B, Hp, 1)
    if can_group:
        pg = p.reshape(B, dims.n_kv, gp, 1, -1)
        o_loc = jnp.einsum(
            "begqk,bked->bqegd", pg.astype(new_v.dtype), new_v
        ).reshape(B, 1, dims.n_heads_padded, dims.hd).astype(jnp.float32)
    else:
        vv_g = _group_full(new_v, dims)
        o_loc = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(vv_g.dtype), vv_g
        ).astype(jnp.float32)
    l = tp_ctx.psum(l_loc)
    o = tp_ctx.psum(o_loc)
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    out = out * hp_mask[None, None, :, None]

    # ---- row-sharded out proj: each shard multiplies its own head slice ----
    lo = me * dims.h_local
    if tp_ctx.enabled:
        out_local = jax.lax.dynamic_slice_in_dim(out, lo, dims.h_local, axis=2)
    else:
        out_local = out
    out_local = out_local.reshape(B, 1, dims.h_local * dims.hd).astype(dt)
    y = tp_ctx.psum(jnp.einsum("bsh,hd->bsd", out_local, params["wo"].astype(dt)))

    new_cache = {"k": new_k, "v": new_v, "pos": new_pos}
    return y, new_cache


def _group_full(k: jax.Array, dims: AttnDims) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, Hp, hd): expand kv to padded q heads."""
    q_per_kv = max(dims.n_heads // dims.n_kv, 1)
    g = jnp.clip(jnp.arange(dims.n_heads_padded) // q_per_kv, 0, dims.n_kv - 1)
    return jnp.take(k, g, axis=2)


def attn_cross_decode(
    x: jax.Array,  # (B, 1, d)
    params: Tree,
    cross_kv: Tree,  # {"k","v"}: (B, T_enc, KV, hd) replicated over model
    cfg: ModelConfig,
    tp_ctx: TPContext,
):
    """Decode-time cross attention over precomputed encoder K/V (no rope)."""
    B, S1, d = x.shape
    dims = AttnDims.resolve(cfg, tp_ctx.size, serve=True)
    dt = x.dtype
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"].astype(dt))
    q = q.reshape(B, 1, dims.h_local, dims.hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
    kf = _expand_kv(cross_kv["k"].astype(dt), dims, tp_ctx)  # (B, T, h_local, hd)
    vf = _expand_kv(cross_kv["v"].astype(dt), dims, tp_ctx)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dims.hd, jnp.float32))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vf.dtype), vf)
    out = out * _head_mask(dims, tp_ctx)[None, None, :, None].astype(dt)
    out = out.reshape(B, 1, dims.h_local * dims.hd)
    return tp_ctx.psum(jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(dt)))
