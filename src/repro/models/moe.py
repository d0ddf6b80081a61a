"""Mixture-of-Experts layer (granite-moe family): top-k router, sorted
dispatch, grouped expert products.

The router (f32 logits, softmax, top-k, renormalised gates) gives each
token ``k`` assignments.  A stable sort of the ``T k`` assignments by
expert puts each expert's rows together, in token order; ``group_sizes``
counts them.  The expert weights then run as three grouped products over
those ragged groups (``kernels/moe_gmm``: ``w_in``, ``w_gate``, and
``w_out`` after the activation), and the rows go back to token order by
the inverse permutation, to be scaled by their gates and summed over the
``k`` assignments of each token.  No expert is padded to a capacity.

``capacity_factor`` still bounds an expert: a row whose rank within its
expert (its position in token order) is ``moe_capacity`` or more gets gate
0, as GShard/Switch drop it.  When the capacity reaches the token count
(``capacity_factor >= n_experts / top_k``) no row can pass it, and the
layer is dropless with no mask at all.

Expert sharding over the model axis picks the first exact fit:
* ``E % tp == 0``      -> expert parallelism: each device runs only the
                          groups of its ``E / tp`` experts (the kernel's
                          group offset) and leaves the other rows 0;
* ``d_ff % tp == 0``   -> tensor parallelism inside every expert
                          (granite-3b: 40 experts, 512 d_ff / 16);
* otherwise replicated.
Either way one psum combines the devices' partial outputs — the same
collective as the dense-MLP path.

Spans (``named_scope``): ``moe_route``, ``moe_dispatch``, ``moe_experts``
and ``moe_combine``.  Counters in the aux dict: ``moe_dropped`` (rows past
capacity) and ``moe_max_load`` (the busiest expert's rows over the mean).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..kernels.moe_gmm.ops import moe_gmm, sum_to
from .layers import Initializer, TPContext, _ACTS

Tree = Any

__all__ = ["moe_init", "moe_specs", "moe_forward", "moe_capacity"]


def moe_capacity(cfg: ModelConfig, tokens: int) -> int:
    c = math.ceil(cfg.top_k * tokens * cfg.capacity_factor / cfg.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for TPU-friendly shapes


def _expert_sharding(cfg: ModelConfig, tp: int) -> str:
    if tp == 1:
        return "replicated"
    if cfg.n_experts % tp == 0:
        return "expert"
    if cfg.d_ff % tp == 0:
        return "ffn"
    return "replicated"


def moe_init(init: Initializer, cfg: ModelConfig) -> Tree:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": init.normal((d, E), 1.0 / math.sqrt(d)),
        "w_in": init.normal((E, d, f), 1.0 / math.sqrt(d)),
        "w_out": init.normal((E, f, d), 1.0 / math.sqrt(f)),
    }
    if cfg.gated_mlp:
        p["w_gate"] = init.normal((E, d, f), 1.0 / math.sqrt(d))
    return p


def moe_specs(cfg: ModelConfig, tp: int, model_axis: str = "model") -> Tree:
    mode = _expert_sharding(cfg, tp)
    m = model_axis
    if mode == "expert":
        win, wout = P(m, None, None), P(m, None, None)
    elif mode == "ffn":
        win, wout = P(None, None, m), P(None, m, None)
    else:
        win, wout = P(None, None, None), P(None, None, None)
    p = {"router": P(None, None), "w_in": win, "w_out": wout}
    if cfg.gated_mlp:
        p["w_gate"] = win
    return p


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, order, inv, k: int):
    """Rows of the ``T k`` assignments in sorted ``order``: token
    ``order // k``.  Its transpose gathers by ``inv`` and sums each token's
    ``k`` rows, with no scatter."""
    return jnp.take(xt, order // k, axis=0)


def _dispatch_fwd(xt, order, inv, k):
    return _dispatch(xt, order, inv, k), (xt[:0], order, inv)


def _dispatch_bwd(k, res, g):
    like, order, inv = res  # like: xt's type, no rows
    dx = jnp.take(g, inv, axis=0).reshape(-1, k, g.shape[-1])
    dx = jnp.sum(dx, axis=1, dtype=jnp.float32).astype(g.dtype)
    return sum_to(dx, like), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` with inverse ``inv``; its
    transpose is the gather by ``inv``."""
    return jnp.take(x, idx, axis=0)


def _permute_fwd(x, idx, inv):
    return _permute(x, idx, inv), (idx, inv)


def _permute_bwd(res, g):
    idx, inv = res
    return jnp.take(g, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def moe_forward(
    x: jax.Array,
    params: Tree,
    cfg: ModelConfig,
    tp_ctx: TPContext,
):
    """x: (B, S, d) replicated -> ((B, S, d) replicated, aux dict)."""
    B, S, d = x.shape
    dt = x.dtype
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    with jax.named_scope("moe_route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            params["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )
        # Switch load-balance + router z-loss
        counts = jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=(0, 1))
        aux_lb = E * jnp.sum(jnp.mean(probs, axis=0) * counts / T)
        aux_z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    with jax.named_scope("moe_dispatch"):
        flat_e = expert_idx.reshape(-1)  # (T*k,) expert of each assignment
        order = jnp.argsort(flat_e, stable=True)  # by expert, then token
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
        group_sizes = counts.astype(jnp.int32)  # exact: counts < 2**24
        gates = gate_vals.reshape(-1)
        C = moe_capacity(cfg, T)
        if C < T:
            starts = jnp.cumsum(group_sizes) - group_sizes
            rank = jnp.arange(T * k) - starts[flat_e[order]]  # in sorted order
            gates = jnp.where(jnp.take(rank, inv) < C, gates, 0.0)
            dropped = jnp.sum(jnp.maximum(group_sizes - C, 0)).astype(jnp.float32)
        else:
            dropped = jnp.float32(0.0)
        xs = _dispatch(xt, order, inv, k)  # (T*k, d), sorted by expert

    aux = {
        "moe_load_balance": aux_lb,
        "moe_router_z": aux_z,
        "moe_dropped": dropped,
        "moe_max_load": jnp.max(counts) * E / (T * k),
        # experts that any kept row reaches (row-sparse gossip tracking):
        # an expert's first row in token order is always within capacity
        "moe_expert_hits": (group_sizes > 0).astype(jnp.float32),
    }

    with jax.named_scope("moe_experts"):
        mode = _expert_sharding(cfg, tp_ctx.size)
        offset = (tp_ctx.axis_index() * params["w_in"].shape[0]
                  if mode == "expert" else None)
        h = moe_gmm(xs, params["w_in"].astype(dt), group_sizes, offset)
        if "w_gate" in params:
            g = moe_gmm(xs, params["w_gate"].astype(dt), group_sizes, offset)
            h = _ACTS[cfg.act](g) * h
        else:
            h = _ACTS[cfg.act](h)
        y = moe_gmm(h, params["w_out"].astype(dt), group_sizes, offset)

    with jax.named_scope("moe_combine"):
        y = _permute(y, inv, order).reshape(T, k, d)  # back to token order
        out = jnp.sum(y.astype(jnp.float32) * gates.reshape(T, k, 1), axis=1)
        if mode != "replicated":
            out = tp_ctx.psum(out)
    return out.reshape(B, S, d).astype(dt), aux
