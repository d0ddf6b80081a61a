"""Model assembly: decoder-only LMs, hybrid (attn+SSM), xLSTM stacks,
encoder-decoder (whisper), VLM-with-stub — all from one ModelConfig.

Layers are organized into **block groups**: maximal runs of consecutive
layers with the same (block kind, attention window).  Each group's params
are stacked on a leading axis and executed with one ``lax.scan`` — compile
time stays O(#groups), and serve caches get per-group capacities (a rolling
``window`` buffer for SWA groups, full capacity only for global-attention
groups — this is what makes hymba/danube long_500k feasible).

Everything below runs either unsharded (tp=1, smoke tests) or inside the
fully-manual shard_map (tp=16 production mesh) — see DESIGN.md §4.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (
    Initializer,
    TPContext,
    embed_lookup,
    embedding_init,
    embedding_specs,
    lm_head_logits,
    mlp_apply,
    mlp_init,
    mlp_specs,
    norm_apply,
    norm_init,
    norm_specs,
    softmax_xent_sharded,
)

Tree = Any

__all__ = [
    "RuntimeConfig",
    "GroupSpec",
    "block_groups",
    "init_params",
    "param_specs",
    "count_params",
    "forward_loss",
    "init_cache",
    "cache_specs",
    "prefill",
    "decode_step",
]


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    dtype: str = "bfloat16"  # activation/compute dtype
    attn_impl: str = "jnp"  # jnp | pallas | pallas_interpret
    mlstm_impl: str = "ref"
    remat: bool = True
    # "full": recompute everything in bwd (collectives re-run);
    # "save_collectives": save TP-psum outputs so the backward pass never
    # re-issues the forward all-reduces (+1 saved (B,S,d) per psum per layer)
    remat_policy: str = "full"
    # decode attention: contract q-head groups against the raw KV cache
    # (no (H/KV)-times K/V materialization); exact for unpadded-head configs
    decode_grouped_gqa: bool = False
    ssm_chunk: int = 128
    mlstm_chunk: int = 128

    @property
    def cdtype(self):
        return jnp.dtype(self.dtype)

    def checkpoint_policy(self):
        if self.remat_policy == "save_collectives":
            return jax.checkpoint_policies.save_only_these_names("tp_psum")
        return None  # nothing saveable (full recompute)


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    kind: str  # dense | moe | hybrid | mlstm | slstm | enc | dec
    window: int  # 0 = full attention (for attn-bearing kinds)
    layers: tuple[int, ...]

    @property
    def count(self) -> int:
        return len(self.layers)

    @property
    def has_attn(self) -> bool:
        return self.kind in ("dense", "moe", "hybrid", "enc", "dec")

    @property
    def has_ssm(self) -> bool:
        return self.kind == "hybrid"


def _layer_kind(cfg: ModelConfig, i: int) -> str:
    if cfg.xlstm:
        return "slstm" if i in cfg.slstm_layers() else "mlstm"
    if cfg.ssm:
        return "hybrid"
    if cfg.moe:
        return "moe"
    return "dense"


def block_groups(cfg: ModelConfig, *, stack: str = "dec") -> list[GroupSpec]:
    """Split layers into maximal same-(kind, window) runs."""
    if stack == "enc":
        n = cfg.n_enc_layers
        sig = lambda i: ("enc", 0)
    else:
        n = cfg.n_layers
        sig = lambda i: (
            "dec" if cfg.arch_kind == "encdec" else _layer_kind(cfg, i),
            cfg.window_for_layer(i),
        )
    groups: list[GroupSpec] = []
    run: list[int] = []
    cur = None
    for i in range(n):
        s = sig(i)
        if s != cur and run:
            groups.append(GroupSpec(kind=cur[0], window=cur[1], layers=tuple(run)))
            run = []
        cur = s
        run.append(i)
    if run:
        groups.append(GroupSpec(kind=cur[0], window=cur[1], layers=tuple(run)))
    return groups


# ---------------------------------------------------------------------------
# Init + specs
# ---------------------------------------------------------------------------


def _layer_init(init: Initializer, cfg: ModelConfig, kind: str, tp: int) -> Tree:
    d = cfg.d_model
    nt = cfg.norm_type
    if kind == "mlstm":
        return {"norm": norm_init(init, nt, d), "mlstm": xlstm_mod.mlstm_init(init, cfg)}
    if kind == "slstm":
        return {"norm": norm_init(init, nt, d), "slstm": xlstm_mod.slstm_init(init, cfg)}
    p = {"attn_norm": norm_init(init, nt, d), "attn": attn.attn_init(init, cfg, tp)}
    if kind == "hybrid":
        p["ssm"] = ssm_mod.ssm_init(init, cfg)
    if kind == "dec" and cfg.arch_kind == "encdec":
        p["cross_norm"] = norm_init(init, nt, d)
        p["cross"] = attn.attn_init(init, cfg, tp)
    if cfg.d_ff > 0:
        p["mlp_norm"] = norm_init(init, nt, d)
        if kind == "moe":
            p["moe"] = moe_mod.moe_init(init, cfg)
        else:
            p["mlp"] = mlp_init(init, d, cfg.d_ff, cfg.gated_mlp)
    return p


def _layer_specs(cfg: ModelConfig, kind: str, tp: int, m: str, serve: bool) -> Tree:
    nt = cfg.norm_type
    if kind == "mlstm":
        return {"norm": norm_specs(nt), "mlstm": xlstm_mod.mlstm_specs(cfg, m)}
    if kind == "slstm":
        return {"norm": norm_specs(nt), "slstm": xlstm_mod.slstm_specs(cfg, m)}
    p = {"attn_norm": norm_specs(nt), "attn": attn.attn_specs(cfg, tp, m, serve=serve)}
    if kind == "hybrid":
        p["ssm"] = ssm_mod.ssm_specs(cfg, m)
    if kind == "dec" and cfg.arch_kind == "encdec":
        p["cross_norm"] = norm_specs(nt)
        p["cross"] = attn.attn_specs(cfg, tp, m, serve=serve)
    if cfg.d_ff > 0:
        p["mlp_norm"] = norm_specs(nt)
        if kind == "moe":
            p["moe"] = moe_mod.moe_specs(cfg, tp, m)
        else:
            p["mlp"] = mlp_specs(cfg.gated_mlp, m)
    return p


def _stack(trees: list[Tree]) -> Tree:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def init_params(key: jax.Array, cfg: ModelConfig, tp: int = 1) -> Tree:
    """Global logical parameters (node axis is added by the train harness)."""
    init = Initializer(key)
    vp = cfg.vocab_padded(tp)
    params: Tree = {"embed": embedding_init(init, vp, cfg.d_model)}
    if cfg.arch_kind == "encdec":
        params["enc"] = {
            f"g{gi}": _stack(
                [_layer_init(init, cfg, g.kind, tp) for _ in g.layers]
            )
            for gi, g in enumerate(block_groups(cfg, stack="enc"))
        }
        params["enc_norm"] = norm_init(init, cfg.norm_type, cfg.d_model)
    params["groups"] = {
        f"g{gi}": _stack([_layer_init(init, cfg, g.kind, tp) for _ in g.layers])
        for gi, g in enumerate(block_groups(cfg))
    }
    params["final_norm"] = norm_init(init, cfg.norm_type, cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": init.normal((cfg.d_model, vp), 1.0 / math.sqrt(cfg.d_model))
        }
    return params


def _prepend(spec_tree: Tree) -> Tree:
    """Prepend the layer-stack axis (None) to every PartitionSpec."""
    return jax.tree.map(
        lambda s: P(None, *s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def param_specs(
    cfg: ModelConfig, tp: int = 1, model_axis: str = "model", serve: bool = False
) -> Tree:
    m = model_axis
    specs: Tree = {"embed": embedding_specs(m)}
    if cfg.arch_kind == "encdec":
        specs["enc"] = {
            f"g{gi}": _prepend(_layer_specs(cfg, g.kind, tp, m, serve))
            for gi, g in enumerate(block_groups(cfg, stack="enc"))
        }
        specs["enc_norm"] = norm_specs(cfg.norm_type)
    specs["groups"] = {
        f"g{gi}": _prepend(_layer_specs(cfg, g.kind, tp, m, serve))
        for gi, g in enumerate(block_groups(cfg))
    }
    specs["final_norm"] = norm_specs(cfg.norm_type)
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"w": P(None, m)}
    return specs


def count_params(cfg: ModelConfig, tp: int = 1) -> int:
    shapes = jax.eval_shape(lambda k: init_params(k, cfg, tp), jax.random.key(0))
    return sum(int(jnp.prod(jnp.asarray(l.shape))) for l in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# Shared block bodies
# ---------------------------------------------------------------------------


def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _block_fwd(
    x, lp, cfg, tp_ctx, rt, g: GroupSpec, *, positions, causal=True, enc_out=None,
    serve=False,
):
    """One layer forward (training/prefill).  Returns (x, aux, cache_entry).

    ``cache_entry`` (serve=True only) is this layer's serve state:
    attention kinds -> (k_full, v_full) over the whole sequence (the prefill
    wrapper slices/rolls it into the sharded cache); recurrent kinds -> the
    final recurrent state; hybrid -> both.
    """
    aux = {}
    entry = None
    nt = cfg.norm_type
    if g.kind == "mlstm":
        h = norm_apply(x, lp["norm"], nt)
        if serve:
            y, st = xlstm_mod.mlstm_forward(
                h, lp["mlstm"], cfg, tp_ctx, chunk=rt.mlstm_chunk,
                impl=rt.mlstm_impl, state=None, return_state=True,
            )
            entry = {"mlstm": st}
        else:
            y = xlstm_mod.mlstm_forward(
                h, lp["mlstm"], cfg, tp_ctx, chunk=rt.mlstm_chunk, impl=rt.mlstm_impl,
            )
        return x + y, aux, entry
    if g.kind == "slstm":
        h = norm_apply(x, lp["norm"], nt)
        if serve:
            y, st = xlstm_mod.slstm_forward(
                h, lp["slstm"], cfg, tp_ctx, state=None, return_state=True
            )
            entry = {"slstm": st}
        else:
            y = xlstm_mod.slstm_forward(h, lp["slstm"], cfg, tp_ctx)
        return x + y, aux, entry

    h = norm_apply(x, lp["attn_norm"], nt)
    attn_kwargs = dict(
        positions=positions, causal=causal, window=g.window,
        attn_impl=rt.attn_impl, remat=rt.remat, serve=serve,
    )
    a = attn.attn_forward(h, lp["attn"], cfg, tp_ctx, return_kv=serve, **attn_kwargs)
    if serve:
        a, kv = a
        entry = {"kv": kv}
    if g.has_ssm:
        if serve:
            s, sst = ssm_mod.ssm_forward(
                h, lp["ssm"], cfg, tp_ctx, chunk=rt.ssm_chunk, return_state=True
            )
            entry["ssm"] = sst
        else:
            s = ssm_mod.ssm_forward(h, lp["ssm"], cfg, tp_ctx, chunk=rt.ssm_chunk)
        x = x + 0.5 * (a + s)  # hymba: fused parallel heads (mean combine)
    else:
        x = x + a
    if g.kind == "dec" and cfg.arch_kind == "encdec" and enc_out is not None:
        c = norm_apply(x, lp["cross_norm"], nt)
        cr = attn.attn_forward(
            c, lp["cross"], cfg, tp_ctx, positions=positions, causal=False,
            window=0, attn_impl=rt.attn_impl, remat=rt.remat, serve=serve,
            kv_source=enc_out, return_kv=serve,
        )
        if serve:
            cr, ckv = cr
            entry["cross_kv"] = ckv
        x = x + cr
    if cfg.d_ff > 0:
        h2 = norm_apply(x, lp["mlp_norm"], nt)
        if g.kind == "moe":
            y2, aux = moe_mod.moe_forward(h2, lp["moe"], cfg, tp_ctx)
        else:
            y2 = mlp_apply(h2, lp["mlp"], cfg.act, tp_ctx)
        x = x + y2
    return x, aux, entry


def _run_groups(
    x, groups_params, cfg, tp_ctx, rt, groups, *, positions, causal=True,
    enc_out=None, serve=False, collect_rows=False,
):
    """Scan each block group; returns (x, aux_totals, per-group cache stacks).

    ``collect_rows=True`` adds ``aux_totals["_row_info"]``: per-MoE-group
    layer-stacked ``(Lg, E)`` expert-hit masks (keyed ``"moe/g<gi>"`` — the
    :class:`repro.sparse.RowTracker` source names), feeding the row-sparse
    gossip channels.  Off by default; the extra aux leaf is dead code XLA
    eliminates when unused.
    """
    aux_tot = {"moe_load_balance": jnp.float32(0.0), "moe_router_z": jnp.float32(0.0)}
    if cfg.moe:
        aux_tot.update(moe_dropped=jnp.float32(0.0), moe_max_load=jnp.float32(0.0))
    entries = {}
    row_info = {}
    for gi, g in enumerate(groups):
        gp = groups_params[f"g{gi}"]

        def body(carry, lp, g=g):
            xx, aux, entry = _block_fwd(
                carry, lp, cfg, tp_ctx, rt, g,
                positions=positions, causal=causal, enc_out=enc_out, serve=serve,
            )
            return xx, (aux, entry)

        if rt.remat and not serve:
            body = jax.checkpoint(
                body, prevent_cse=False, policy=rt.checkpoint_policy()
            )
        x, (auxs, entry_stack) = jax.lax.scan(body, x, gp)
        for k in aux_tot:
            if auxs and k in auxs:
                if k == "moe_max_load":  # the busiest expert of any layer
                    aux_tot[k] = jnp.maximum(aux_tot[k], jnp.max(auxs[k]))
                else:
                    aux_tot[k] = aux_tot[k] + jnp.sum(auxs[k])
        if collect_rows and auxs and "moe_expert_hits" in auxs:
            row_info[f"moe/g{gi}"] = auxs["moe_expert_hits"]  # (Lg, E)
        if serve:
            entries[f"g{gi}"] = entry_stack
    if collect_rows:
        aux_tot["_row_info"] = row_info
    return x, aux_tot, entries


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg, tp_ctx, rt):
    dt = rt.cdtype
    tokens = batch["tokens"]
    B, S = tokens.shape
    vp = params["embed"]["table"].shape[0] * (tp_ctx.size if tp_ctx.enabled else 1)
    x = embed_lookup(tokens, params["embed"]["table"].astype(dt), tp_ctx, vp)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(dt)
        x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
    if cfg.rope_theta == 0:  # whisper-style absolute sinusoidal positions
        pos = jnp.arange(S)
        x = x + _sinusoid(pos, cfg.d_model)[None].astype(dt)
    return x


def _encode(params, batch, cfg, tp_ctx, rt):
    """Whisper encoder over stub frame embeddings."""
    dt = rt.cdtype
    frames = batch["enc_frames"].astype(dt)  # (B, T_enc, d) — conv stub output
    x = frames + _sinusoid(jnp.arange(frames.shape[1]), cfg.d_model)[None].astype(dt)
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1])[None], frames.shape[:2])
    x, _, _ = _run_groups(
        x, params["enc"], cfg, tp_ctx, rt, block_groups(cfg, stack="enc"),
        positions=pos, causal=False,
    )
    return norm_apply(x, params["enc_norm"], cfg.norm_type)


def _lm_head_w(params, cfg, tp_ctx, rt):
    if cfg.tie_embeddings:
        return params["embed"]["table"].astype(rt.cdtype).T
    return params["lm_head"]["w"].astype(rt.cdtype)


def forward_loss(params, batch, cfg: ModelConfig, tp_ctx: TPContext, rt: RuntimeConfig,
                 *, collect_rows=False):
    """batch: tokens (B,S), targets (B,S) [, patch_embeds, enc_frames, mask].

    ``collect_rows=True`` adds ``metrics["_row_info"]`` (see
    :func:`_run_groups`) for row-sparse gossip tracking."""
    x = _embed_inputs(params, batch, cfg, tp_ctx, rt)
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    enc_out = None
    if cfg.arch_kind == "encdec":
        enc_out = _encode(params, batch, cfg, tp_ctx, rt)
    x, aux, _ = _run_groups(
        x, params["groups"], cfg, tp_ctx, rt, block_groups(cfg),
        positions=positions, causal=True, enc_out=enc_out,
        collect_rows=collect_rows,
    )
    x = norm_apply(x, params["final_norm"], cfg.norm_type)
    with jax.named_scope("lm_head"):
        logits = lm_head_logits(x, _lm_head_w(params, cfg, tp_ctx, rt))
        vp = cfg.vocab_padded(tp_ctx.size)
        loss = softmax_xent_sharded(
            logits.reshape(B * S, -1),
            batch["targets"].reshape(-1),
            tp_ctx,
            vocab_size=cfg.vocab_size,
            vocab_padded=vp,
            mask=(batch["mask"].reshape(-1) if "mask" in batch else None),
        )
    total = loss + cfg.router_aux_weight * aux["moe_load_balance"] + 1e-3 * aux[
        "moe_router_z"
    ]
    metrics = {"xent": loss, **aux}
    return total, metrics


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def _group_capacity(g: GroupSpec, cfg: ModelConfig, target_len: int, tp: int) -> int:
    cap = min(g.window, target_len) if g.window > 0 else target_len
    return ((cap + tp - 1) // tp) * tp


def init_cache(
    cfg: ModelConfig, batch: int, target_len: int, tp: int, rt: RuntimeConfig
) -> Tree:
    """Serve cache pytree: one sub-dict per block group (layer-stacked)."""
    cache: Tree = {}
    for gi, g in enumerate(block_groups(cfg)):
        c: Tree = {}
        if g.has_attn:
            cap = _group_capacity(g, cfg, target_len, tp)
            c["kv"] = attn.init_kv_cache(cfg, g.count, batch, cap, tp, rt.cdtype)
        if g.has_ssm:
            st = ssm_mod.init_ssm_state(cfg, g.count, batch, tp)
            c["ssm"] = {"h": st["h"], "conv": st["conv"]}
        if g.kind == "mlstm":
            c["mlstm"] = xlstm_mod.init_mlstm_state(cfg, g.count, batch, tp)
        if g.kind == "slstm":
            c["slstm"] = xlstm_mod.init_slstm_state(cfg, g.count, batch)
        if g.kind == "dec" and cfg.arch_kind == "encdec":
            dims = attn.AttnDims.resolve(cfg, tp, serve=True)
            c["cross_kv"] = {
                "k": jnp.zeros((g.count, batch, cfg.enc_seq, dims.n_kv, dims.hd), rt.cdtype),
                "v": jnp.zeros((g.count, batch, cfg.enc_seq, dims.n_kv, dims.hd), rt.cdtype),
            }
        cache[f"g{gi}"] = c
    return cache


def cache_specs(cfg: ModelConfig, batch_axes, model_axis: str = "model") -> Tree:
    specs: Tree = {}
    for gi, g in enumerate(block_groups(cfg)):
        c: Tree = {}
        if g.has_attn:
            c["kv"] = attn.kv_cache_specs(batch_axes, model_axis)
        if g.has_ssm:
            c["ssm"] = ssm_mod.ssm_state_specs(batch_axes, model_axis)
        if g.kind == "mlstm":
            c["mlstm"] = xlstm_mod.mlstm_state_specs(batch_axes, model_axis)
        if g.kind == "slstm":
            c["slstm"] = xlstm_mod.slstm_state_specs(batch_axes)
        if g.kind == "dec" and cfg.arch_kind == "encdec":
            c["cross_kv"] = {
                "k": P(None, batch_axes, None, None, None),
                "v": P(None, batch_axes, None, None, None),
            }
        specs[f"g{gi}"] = c
    return specs


def _roll_into_cache(k_full, v_full, cap: int, tp_ctx: TPContext):
    """(Lg, B, S, KV, hd) full-sequence kv -> sharded rolling cache.

    Slot j holds the largest position p < S with p %% cap == j (or empty).
    Static index table (S, cap known at trace); the device then slices its
    own contiguous chunk of slots.
    """
    import numpy as np

    Lg, B, S = k_full.shape[0], k_full.shape[1], k_full.shape[2]
    j = np.arange(cap)
    p = cap * ((S - 1 - j) // cap) + j
    p = np.where((p >= 0) & (p < S), p, -1)
    idx = jnp.asarray(np.maximum(p, 0), jnp.int32)
    valid = jnp.asarray(p >= 0)
    kc = jnp.take(k_full, idx, axis=2)
    vc = jnp.take(v_full, idx, axis=2)
    pos = jnp.where(valid, jnp.asarray(np.maximum(p, 0), jnp.int32), -1)
    pos = jnp.broadcast_to(pos[None, None], (Lg, B, cap))
    if tp_ctx.enabled:
        s_local = cap // tp_ctx.size
        lo = tp_ctx.axis_index() * s_local
        kc = jax.lax.dynamic_slice_in_dim(kc, lo, s_local, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(vc, lo, s_local, axis=2)
        pos = jax.lax.dynamic_slice_in_dim(pos, lo, s_local, axis=2)
    return {"k": kc, "v": vc, "pos": pos}


def prefill(
    params, batch, cfg: ModelConfig, tp_ctx: TPContext, rt: RuntimeConfig,
    *, target_len: int | None = None,
):
    """Full-sequence prefill: returns (last-token logits (B, Vp), cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    target_len = target_len or S
    x = _embed_inputs(params, batch, cfg, tp_ctx, rt)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    enc_out = None
    if cfg.arch_kind == "encdec":
        enc_out = _encode(params, batch, cfg, tp_ctx, rt)
    x, _, entries = _run_groups(
        x, params["groups"], cfg, tp_ctx, rt, block_groups(cfg),
        positions=positions, causal=True, enc_out=enc_out, serve=True,
    )
    x = norm_apply(x, params["final_norm"], cfg.norm_type)
    # logits stay vocab-sharded over the model axis (the jit-level output is
    # assembled by the out_spec; no gather collective needed)
    logits = lm_head_logits(x[:, -1], _lm_head_w(params, cfg, tp_ctx, rt))

    cache: Tree = {}
    for gi, g in enumerate(block_groups(cfg)):
        entry = entries[f"g{gi}"]
        c: Tree = {}
        if g.has_attn:
            cap = _group_capacity(g, cfg, target_len, tp_ctx.size)
            kf, vf = entry["kv"]
            c["kv"] = _roll_into_cache(kf, vf, cap, tp_ctx)
        if g.has_ssm:
            c["ssm"] = entry["ssm"]
        if g.kind == "mlstm":
            c["mlstm"] = entry["mlstm"]
        if g.kind == "slstm":
            c["slstm"] = entry["slstm"]
        if "cross_kv" in (entry or {}):
            ck, cv = entry["cross_kv"]
            c["cross_kv"] = {"k": ck, "v": cv}
        cache[f"g{gi}"] = c
    return logits, cache


def decode_step(
    params, tokens, cache, t, cfg: ModelConfig, tp_ctx: TPContext, rt: RuntimeConfig,
    *, target_len: int,
):
    """One-token decode.  tokens: (B, 1); t: absolute position of the new
    token, int32 scalar or per-slot ``(B,)`` vector (continuous batching
    serves requests whose timelines are independent — each slot carries its
    own position).  Returns (logits (B, Vp), new_cache)."""
    dt = rt.cdtype
    B = tokens.shape[0]
    vp_local = params["embed"]["table"].shape[0]
    vp = vp_local * (tp_ctx.size if tp_ctx.enabled else 1)
    x = embed_lookup(tokens, params["embed"]["table"].astype(dt), tp_ctx, vp)
    if cfg.rope_theta == 0:
        tvec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
        x = x + _sinusoid(tvec[:, None], cfg.d_model).astype(dt)

    new_cache: Tree = {}
    for gi, g in enumerate(block_groups(cfg)):
        gp = params["groups"][f"g{gi}"]
        cg = cache[f"g{gi}"]
        cap = _group_capacity(g, cfg, target_len, tp_ctx.size) if g.has_attn else 0

        def body(carry, xs, g=g, cap=cap):
            xx = carry
            lp, cl = xs
            nc = dict(cl)
            nt = cfg.norm_type
            if g.kind == "mlstm":
                h = norm_apply(xx, lp["norm"], nt)
                y, st = xlstm_mod.mlstm_decode_step(h, lp["mlstm"], cl["mlstm"], cfg, tp_ctx)
                nc["mlstm"] = st
                return xx + y, nc
            if g.kind == "slstm":
                h = norm_apply(xx, lp["norm"], nt)
                y, st = xlstm_mod.slstm_decode_step(h, lp["slstm"], cl["slstm"], cfg, tp_ctx)
                nc["slstm"] = st
                return xx + y, nc
            h = norm_apply(xx, lp["attn_norm"], nt)
            a, nkv = attn.attn_decode_step(
                h, lp["attn"], cl["kv"], cfg, tp_ctx,
                t=t, window=g.window, capacity=cap,
                grouped=rt.decode_grouped_gqa,
            )
            nc["kv"] = nkv
            if g.has_ssm:
                s, sst = ssm_mod.ssm_decode_step(h, lp["ssm"], cl["ssm"], cfg, tp_ctx)
                nc["ssm"] = sst
                xx = xx + 0.5 * (a + s)
            else:
                xx = xx + a
            if g.kind == "dec" and cfg.arch_kind == "encdec":
                c2 = norm_apply(xx, lp["cross_norm"], nt)
                xx = xx + attn.attn_cross_decode(
                    c2, lp["cross"], cl["cross_kv"], cfg, tp_ctx
                )
            if cfg.d_ff > 0:
                h2 = norm_apply(xx, lp["mlp_norm"], nt)
                if g.kind == "moe":
                    y2, _ = moe_mod.moe_forward(h2, lp["moe"], cfg, tp_ctx)
                else:
                    y2 = mlp_apply(h2, lp["mlp"], cfg.act, tp_ctx)
                xx = xx + y2
            return xx, nc

        x, ncg = jax.lax.scan(body, x, (gp, cg))
        new_cache[f"g{gi}"] = ncg

    x = norm_apply(x, params["final_norm"], cfg.norm_type)
    logits = lm_head_logits(x[:, -1], _lm_head_w(params, cfg, tp_ctx, rt))
    return logits, new_cache
