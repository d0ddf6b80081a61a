"""Plain float32 reference of the timed training steps.

It follows the configuration file alone and imports nothing of the
program: a decoder (RMSNorm, rotary GQA attention with optional per-head
q/k norms, SwiGLU MLP or top-k mixture of experts with a per-expert
capacity in token order), the mean token cross entropy plus the router
terms, jax autodiff for the gradient, and DecentLaM's update with a dense
mixing matrix over the nodes:

    payload_i = x_i - lr g_i ;  mix_i = sum_j W_ij payload_j
    m_i <- beta m_i + (x_i - mix_i) / lr ;  x_i <- x_i - lr m_i

Weights come from ``weights.build_params`` with the run's seed.  Every
matrix product runs at ``Precision.HIGHEST`` in float32.  ``precision=
"fp8"`` is the control: the two operands of every product are rounded to
float8 e4m3 (per-tensor scale, gradient passed straight through) before
it.  ``fault`` plants one of the faults the check must catch:
``"half_batch"`` (the loss over half of each node's tokens) or
``"no_exchange"`` (each node mixes only with itself).

Layers, each sequence's attention and each expert run under
``jax.checkpoint``, and the vocabulary projection in blocks of rows, so
the reference fits on the chip beside nothing else.  Nodes are stacked on a leading axis, sharded
over the devices given.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from check import flat_norms, leaf_norms
from weights import build_params, dims, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
XENT_ROWS = 1024


# ---------------------------------------------------------------------------
# matrix products at the reference's precision
# ---------------------------------------------------------------------------


def _round_fp8(x):
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def make_mm(precision: str):
    if precision not in ("f32", "fp8"):
        raise ValueError(precision)

    def mm(spec, a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if precision == "fp8":
            a, b = _round_fp8(a), _round_fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return mm


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def capacity(cfg: dict, tokens: int) -> int:
    """Slots per expert: ceil(k T cf / E), rounded up to a multiple of 8."""
    m = dims(cfg)
    c = math.ceil(m["k"] * tokens * float(cfg["capacity_factor"]) / m["e"])
    return max(8, -(-c // 8) * 8)


def node_loss(p, tokens, targets, cfg: dict, mm):
    """Total loss of one node on its (B, S) tokens."""
    m = dims(cfg)
    B, S = tokens.shape
    H, KV, hd = m["h"], m["kv"], m["hd"]
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    att_scale = float(cfg.get("attention_multiplier") or 1.0 / math.sqrt(hd))
    resid = float(cfg.get("residual_multiplier", 1.0))
    table = p["embed"]["table"]
    x = jnp.take(table, tokens, axis=0) * float(cfg.get("embedding_multiplier", 1.0))
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]

    def attention(h, a):
        def one(hs):  # (S, d) -> (S, H*hd), one sequence at a time
            q = mm("sd,dh->sh", hs, a["wq"]).reshape(S, H, hd)
            k = mm("sd,dh->sh", hs, a["wk"]).reshape(S, KV, hd)
            v = mm("sd,dh->sh", hs, a["wv"]).reshape(S, KV, hd)
            if cfg.get("qk_norm"):
                q = _rms(q, a["q_norm"], eps)
                k = _rms(k, a["k_norm"], eps)
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
            k = jnp.repeat(k, H // KV, axis=1)
            v = jnp.repeat(v, H // KV, axis=1)
            s = mm("qhd,khd->hqk", q, k) * att_scale
            s = jnp.where(causal[None], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            return mm("hqk,khd->qhd", w, v).reshape(S, H * hd)

        o = jax.lax.map(jax.checkpoint(one), h)
        return mm("bsh,hd->bsd", o, a["wo"])

    def moe(h, e):
        T = B * S
        E, k = m["e"], m["k"]
        ht = h.reshape(T, -1)
        r = mm("td,de->te", ht, e["router"])
        probs = jax.nn.softmax(r, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)
        gates = topv / jnp.maximum(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)
        chosen = jnp.sum(jax.nn.one_hot(topi, E, dtype=jnp.float32), axis=1)
        lb = E * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(chosen, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(r, axis=-1)))
        flat = topi.reshape(-1)
        onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)
        slot = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                   flat[:, None], axis=1)[:, 0]
        keep = slot < capacity(cfg, T)
        comb = jnp.zeros((T, E), jnp.float32).at[
            jnp.repeat(jnp.arange(T), k), flat].add(
                jnp.where(keep, gates.reshape(-1), 0.0))

        def expert(acc, w):
            wi, wg, wo, c = w
            y = mm("tf,fd->td",
                   jax.nn.silu(mm("td,df->tf", ht, wg)) * mm("td,df->tf", ht, wi), wo)
            return acc + c[:, None] * y, None

        y, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(ht),
                            (e["w_in"], e["w_gate"], e["w_out"], comb.T))
        return y.reshape(B, S, -1), lb, z

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"]["scale"], eps)
        x = x + resid * attention(h, lp["attn"])
        h = _rms(x, lp["mlp_norm"]["scale"], eps)
        if "moe" in lp:
            y, lb, z = moe(h, lp["moe"])
        else:
            f = lp["mlp"]
            y = mm("bsf,fd->bsd", jax.nn.silu(mm("bsd,df->bsf", h, f["w_gate"]))
                   * mm("bsd,df->bsf", h, f["w_in"]), f["w_out"])
            lb = z = jnp.float32(0.0)
        return x + resid * y, (lb, z)

    x, (lbs, zs) = jax.lax.scan(jax.checkpoint(layer), x, p["groups"]["g0"])
    x = _rms(x, p["final_norm"]["scale"], eps)
    w_out = table.T if cfg["tie_word_embeddings"] else p["lm_head"]["w"]
    inv = 1.0 / float(cfg.get("logits_scaling", 1.0))
    rows = x.reshape(B * S, -1)
    tgt = targets.reshape(-1)
    n_blk = -(-rows.shape[0] // XENT_ROWS)
    pad = n_blk * XENT_ROWS - rows.shape[0]
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    valid = jnp.pad(jnp.ones_like(tgt, jnp.float32), (0, pad))
    tgt = jnp.pad(tgt, (0, pad))

    @jax.checkpoint
    def block(xb, tb, vb):
        lg = mm("td,dv->tv", xb, w_out) * inv
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * vb)

    def body(acc, xs):
        return acc + block(*xs), None

    total, _ = jax.lax.scan(
        body, jnp.float32(0.0),
        (rows.reshape(n_blk, XENT_ROWS, -1), tgt.reshape(n_blk, XENT_ROWS),
         valid.reshape(n_blk, XENT_ROWS)))
    xent = total / (B * S)
    return (xent + float(cfg.get("router_aux_loss_coef", 0.0)) * jnp.sum(lbs)
            + float(cfg.get("router_z_loss_coef", 0.0)) * jnp.sum(zs))


# ---------------------------------------------------------------------------
# mixing matrix and norms
# ---------------------------------------------------------------------------


def mixing_matrix(topology: str, n: int) -> np.ndarray:
    """Metropolis weights of the named static topology."""
    if n == 1:
        return np.ones((1, 1))
    if topology != "ring":
        raise ValueError(f"the reference knows the ring, not {topology!r}")
    adj = np.zeros((n, n), bool)
    for i in range(n):
        adj[i, (i + 1) % n] = adj[i, (i - 1) % n] = True
    deg = adj.sum(1)
    W = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            if adj[i, j]:
                W[i, j] = 1.0 / (1.0 + max(deg[i], deg[j]))
        W[i, i] = 1.0 - W[i].sum()
    return W


# ---------------------------------------------------------------------------
# the steps
# ---------------------------------------------------------------------------


def run(cfg: dict, traffic: dict, seed: int, batches: list, *, devices,
        precision: str = "f32", fault: str | None = None) -> dict:
    """Follow ``len(batches)`` DecentLaM steps from the seed's weights.

    Returns host values: ``loss`` (node mean per step), ``grad`` (norms of
    the momentum after step 1, which is the gradient as the optimizer got
    it), ``raw_grad`` (norms of each node's own step-1 gradient) and
    ``change`` (norms of the parameters' change after the last step).
    """
    n = int(traffic["nodes"])
    mesh = Mesh(np.array(devices[:n]), ("node",))
    node = NamedSharding(mesh, P("node"))
    lr = float(traffic["lr"])
    beta = float(traffic["momentum"])
    W = mixing_matrix(traffic["topology"], n)
    if fault == "no_exchange":
        W = np.eye(n)
    elif fault not in (None, "half_batch"):
        raise ValueError(fault)
    W = jnp.asarray(W, jnp.float32)
    mm = make_mm(precision)

    def stacked_params(key):
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
                            build_params(key, cfg))

    key = seed_key(seed)
    x = jax.jit(stacked_params, out_shardings=node)(key)
    mom = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                  out_shardings=node)(x)

    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.vmap(jax.value_and_grad(
            lambda p, t, y: node_loss(p, t, y, cfg, mm))),
            out_shardings=(node, node))

        def update(xl, ml, gl):
            pay = xl - lr * gl
            mix = jnp.einsum("ij,j...->i...", W, pay, precision=HIGHEST)
            ml = beta * ml + (xl - mix) / max(lr, 1e-12)
            return xl - lr * ml, ml

        # donated: each leaf's old copies are freed as its new ones are made
        update = jax.jit(update, donate_argnums=(0, 1))
        norms = jax.jit(leaf_norms)
        out = {"loss": []}
        for k, b in enumerate(batches):
            B = int(traffic["per_node_batch"])
            S = int(traffic["seq_len"])
            tok = np.asarray(b["tokens"]).reshape(n, B, S)
            tgt = np.asarray(b["targets"]).reshape(n, B, S)
            if fault == "half_batch":
                if B > 1:
                    tok, tgt = tok[:, : B // 2], tgt[:, : B // 2]
                else:
                    tok, tgt = tok[:, :, : S // 2], tgt[:, :, : S // 2]
            loss, g = grad_fn(x, jax.device_put(tok, node), jax.device_put(tgt, node))
            out["loss"].append(float(jnp.mean(loss)))
            if k == 0:
                out["raw_grad"] = flat_norms(norms(g))
            xs, ms = [], []
            for xl, ml, gl in zip(jax.tree.leaves(x), jax.tree.leaves(mom),
                                  jax.tree.leaves(g)):
                a, c = update(xl, ml, gl)
                gl.delete()
                xs.append(a)
                ms.append(c)
            del g
            treedef = jax.tree.structure(x)
            x = jax.tree.unflatten(treedef, xs)
            mom = jax.tree.unflatten(treedef, ms)
            if k == 0:
                out["grad"] = flat_norms(norms(mom))
        change = jax.jit(lambda t, kk: leaf_norms(
            jax.tree.map(lambda a, b: a - b, t, stacked_params(kk))))
        out["change"] = flat_norms(change(x, key))
    return out
