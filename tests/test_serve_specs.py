"""Serve spec derivation: the replicated-batch fallback.

``_batch_axes`` only shards the serve batch over the node axes when the
global batch divides the node-axis extent; otherwise (e.g. a single
request on an 8-way mesh) the batch stays **replicated** while the params
keep their model sharding — both prefill and decode specs must degrade
that way.  Execution parity of the fallback path runs in
``tests/scripts/distributed_serve.py`` (prefill-b1 / decode-b1 sections).
"""

import types

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import tiny_lm
from repro.train.serve import _batch_axes, serve_specs

MESH8 = types.SimpleNamespace(shape={"data": 8, "model": 1})
CFG = tiny_lm(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
              vocab_size=64)


def test_batch_axes_divisibility():
    assert _batch_axes(8, ("data",), MESH8) == ("data",)
    assert _batch_axes(16, ("data",), MESH8) == ("data",)
    for gb in (1, 3, 4, 12):  # indivisible or undersized -> replicated
        assert _batch_axes(gb, ("data",), MESH8) is None
    # multi-axis fleets multiply the extents
    mesh = types.SimpleNamespace(shape={"data": 4, "fleet": 2, "model": 1})
    assert _batch_axes(8, ("data", "fleet"), mesh) == ("data", "fleet")
    assert _batch_axes(4, ("data", "fleet"), mesh) is None


def test_serve_specs_replicated_fallback():
    """global_batch=1 on an 8-way node axis: token + cache batch dims drop
    to None (replicated) for both prefill and decode consumers, while the
    param specs are untouched by the batch decision."""
    p8, c8, tok8, ba8 = serve_specs(CFG, MESH8, global_batch=8)
    p1, c1, tok1, ba1 = serve_specs(CFG, MESH8, global_batch=1)
    assert ba8 == ("data",) and ba1 is None
    assert tok8 == P(("data",), None) and tok1 == P(None, None)
    # params: identical specs either way (model sharding only)
    assert jax.tree.map(
        lambda a, b: a == b, p8, p1, is_leaf=lambda x: isinstance(x, P)
    )

    def batch_dim(spec):
        # cache leaves are (Lg, B, ...); PartitionSpec normalises a
        # one-axis tuple ("data",) to "data", so compare normalised specs
        return P(spec[1])

    for leaf in jax.tree.leaves(c8, is_leaf=lambda x: isinstance(x, P)):
        assert batch_dim(leaf) == P(("data",)), leaf
    for leaf in jax.tree.leaves(c1, is_leaf=lambda x: isinstance(x, P)):
        assert batch_dim(leaf) == P(None), leaf
