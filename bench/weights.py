"""Random weights from the seed, made by the benchmark on the device.

The parameter tree is written out here from the configuration file, under
the names the program's model reads; the harness checks at set-up that
the program's own tree has exactly these leaves and shapes.  Both the
program (as its initial state) and the reference take their weights from
:func:`make_params`, so neither takes anything the other has made.

Per leaf: RMSNorm scales (stored as ``1 + scale``) draw ``0.1 N(0, 1)``,
the embedding table ``0.02 N(0, 1)``, every matrix ``N(0, 1) / sqrt(fan_in)``
with ``fan_in`` its second-to-last axis.  Layers are stacked on a leading
axis, as the program scans them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_LEAVES = ("scale", "q_norm", "k_norm")


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return dict(
        d=d, h=h, kv=int(cfg["num_key_value_heads"]),
        hd=int(cfg.get("head_dim") or d // h),
        f=int(cfg["intermediate_size"]), v=int(cfg["vocab_size"]),
        L=int(cfg["num_hidden_layers"]), e=int(cfg.get("num_local_experts", 0)),
        k=int(cfg.get("num_experts_per_tok", 0)),
    )


def param_shapes(cfg: dict) -> dict:
    """Nested dict of leaf shapes, in the program's naming."""
    m = dims(cfg)
    d, L, f = m["d"], m["L"], m["f"]
    attn = {
        "wq": (L, d, m["h"] * m["hd"]),
        "wk": (L, d, m["kv"] * m["hd"]),
        "wv": (L, d, m["kv"] * m["hd"]),
        "wo": (L, m["h"] * m["hd"], d),
    }
    if cfg.get("qk_norm"):
        attn["q_norm"] = (L, m["hd"])
        attn["k_norm"] = (L, m["hd"])
    layer = {"attn_norm": {"scale": (L, d)}, "attn": attn,
             "mlp_norm": {"scale": (L, d)}}
    if m["e"]:
        e = m["e"]
        layer["moe"] = {"router": (L, d, e), "w_in": (L, e, d, f),
                        "w_gate": (L, e, d, f), "w_out": (L, e, f, d)}
    else:
        layer["mlp"] = {"w_in": (L, d, f), "w_gate": (L, d, f),
                        "w_out": (L, f, d)}
    tree = {"embed": {"table": (m["v"], d)}, "groups": {"g0": layer},
            "final_norm": {"scale": (d,)}}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = {"w": (d, m["v"])}
    return tree


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _leaf(key, path, shape):
    name = path[-1].key
    z = jax.random.normal(key, shape, jnp.float32)
    if name in NORM_LEAVES:
        return 0.1 * z
    if name == "table":
        return 0.02 * z
    return z / jnp.sqrt(jnp.float32(shape[-2]))


def build_params(key: jax.Array, cfg: dict) -> dict:
    """Traceable: the f32 parameter tree drawn from ``key``."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    leaves = [_leaf(jax.random.fold_in(key, i), path, shape)
              for i, (path, shape) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_params(seed: int, cfg: dict, sharding=None) -> dict:
    """The weights of ``seed``, made in one jitted call on the device."""
    fn = jax.jit(lambda k: build_params(k, cfg), out_shardings=sharding)
    return fn(seed_key(seed))
