"""Operations and bytes the algorithm needs, counted from the shapes.

``flops_per_token``: the forward and backward passes' matrix products per
trained token, with nothing recomputed: 6 x the parameters a token meets
in a product (attention projections, the MLP or its top-k experts and the
router, the vocabulary projection), plus 12 x layers x query width x
sequence for the attention scores and values (PaLM's count, causal mask
not halved).

``update_bytes_per_node``: the bytes DecentLaM's two plane stages must move
per step and node.  Each leaf fills whole rows of ``LANES`` f32 lanes; the
first stage reads x and g and writes the payload (3 arrays), the second
reads x, the mix and m and writes x and m (5 arrays).
"""

from __future__ import annotations

import math

from weights import dims, param_shapes

LANES = 1024
F32 = 4
DECENTLAM_ARRAYS = 3 + 5


def flops_per_token(cfg: dict, seq_len: int) -> float:
    m = dims(cfg)
    d, qw, kvw = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    per_layer = d * qw + 2 * d * kvw + qw * d
    if m["e"]:
        per_layer += d * m["e"] + m["k"] * 3 * d * m["f"]
    else:
        per_layer += 3 * d * m["f"]
    n = m["L"] * per_layer + m["v"] * d
    return 6.0 * n + 12.0 * m["L"] * qw * seq_len


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


def plane_rows(cfg: dict) -> int:
    return sum(-(-math.prod(s) // LANES) for s in _leaves(param_shapes(cfg)))


def update_bytes_per_node(cfg: dict) -> float:
    return float(plane_rows(cfg) * LANES * F32 * DECENTLAM_ARRAYS)
