"""The jnp attention core (q blocks over their visible key span) against the
full-matrix oracle: outputs and the gradients in q, k and v, taken under an
outer ``jax.checkpoint`` as the layer scan applies it, in float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ref import reference_attention
from repro.models import attention as A

B, H, HD, BQ = 2, 2, 16, 16

# (name, Sq, Sk, causal, window); at 200 rows the 13 blocks of 16 form
# seven spans of two blocks each (the last a ragged 8 rows), mapped in turn
CASES = [
    ("causal", 64, 64, True, 0),
    ("noncausal", 64, 64, False, 0),
    ("static_window", 64, 64, True, 20),
    ("static_window_noncausal", 64, 64, False, 20),
    ("ragged_sq", 56, 56, True, 0),
    ("ragged_sq_window", 56, 56, True, 24),
    ("cross_attention", 40, 24, False, 0),
    ("spans_causal", 200, 200, True, 0),
    ("spans_noncausal", 200, 200, False, 0),
    ("spans_window", 200, 200, True, 40),
    ("spans_cross_attention", 200, 72, False, 0),
]


def _inputs(Sq, Sk, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((B, n, H, HD)), jnp.float32)
               for n in (Sq, Sk, Sk))
    ct = jnp.asarray(rng.standard_normal((B, Sq, H, HD)), jnp.float32)
    return q, k, v, ct


def _loss_and_grads(attend, q, k, v, ct):
    loss = jax.checkpoint(lambda q, k, v: jnp.sum(attend(q, k, v) * ct))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _count(jaxpr, names) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name in names
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, names)
    return n


def _checkpoints(jaxpr) -> int:
    return _count(jaxpr, ("checkpoint", "remat", "remat2"))


def _block_calls(Sq, Sk, bq, causal, window) -> int:
    """Places the core calls a block: one per span whose whole blocks are
    mapped, one per block of any other span, and one per ragged tail."""
    n = 0
    for q0, q1, _, _ in A._block_spans(Sq, Sk, bq, causal, window):
        whole = (q1 - q0) // bq
        n += 1 + (q1 - q0 - whole * bq > 0) if whole > 1 else -(-(q1 - q0) // bq)
    return n


@pytest.mark.parametrize("budget", ["saved", "per_block_checkpoint"])
@pytest.mark.parametrize("name,Sq,Sk,causal,window", CASES,
                         ids=[c[0] for c in CASES])
def test_core_matches_reference(monkeypatch, budget, name, Sq, Sk, causal,
                                window):
    # both sides of the rule: probabilities saved, or each block checkpointed
    monkeypatch.setattr(A, "PROBS_BUDGET_BYTES",
                        2**30 if budget == "saved" else 0)
    q, k, v, ct = _inputs(Sq, Sk)

    def core(q, k, v):
        return A.attention_core(q, k, v, causal=causal, window=window,
                                q_block=BQ, remat=True)

    ref = functools.partial(reference_attention, causal=causal, window=window)
    np.testing.assert_allclose(jax.jit(core)(q, k, v), ref(q, k, v),
                               atol=1e-5, rtol=1e-5)
    got_loss, got = _loss_and_grads(core, q, k, v, ct)
    want_loss, want = _loss_and_grads(ref, q, k, v, ct)
    np.testing.assert_allclose(got_loss, want_loss, atol=1e-4, rtol=1e-5)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-5, rtol=1e-5)

    ckpts = _checkpoints(jax.make_jaxpr(core)(q, k, v).jaxpr)
    assert ckpts == (0 if budget == "saved"
                     else _block_calls(Sq, Sk, BQ, causal, window))


def test_budget_rule_reads_the_shapes():
    """At the qwen3-0.6b cell's shapes (B 2, S 2048, 16 heads of 128, bf16)
    one layer's saved probabilities fit the budget, so no block carries a
    checkpoint; at four times the sequence they do not, and each of the
    eight spans maps its two 512-row blocks through one checkpointed call."""
    def n_checkpoints(S):
        x = jax.ShapeDtypeStruct((2, S, 16, 128), jnp.bfloat16)
        core = functools.partial(A.attention_core, causal=True, remat=True)
        return _checkpoints(jax.make_jaxpr(core)(x, x, x).jaxpr)

    assert n_checkpoints(2048) == 0
    assert n_checkpoints(8192) == 8


@pytest.mark.parametrize("Sq,Sk,bq,causal,window,share", [
    (2048, 2048, 512, True, 0, 0.625),
    (2048, 2048, 256, True, 0, 0.5625),
    (2048, 2048, 512, False, 0, 1.0),
    (2048, 2048, 2048, True, 0, 1.0),
    (2048, 2048, 512, True, 4096, 0.625),
    # rows [512i, 512i+512) see keys from block i-2 on: at most 3 blocks
    (4096, 4096, 512, True, 1024, (1 + 2 + 3 * 6) / 64),
    (1500, 1500, 512, False, 0, 1.0),
    (48, 80, 16, False, 0, 1.0),
    # more than eight blocks: eight spans of whole blocks, each scored
    # against the key prefix of its last row
    (8192, 8192, 512, True, 0, 0.5625),
    (32768, 32768, 512, True, 0, 0.5625),
    (200, 200, 16, True, 0, (32 * 32 * (1 + 2 + 3 + 4 + 5 + 6) + 8 * 200) / 200**2),
])
def test_score_block_share(Sq, Sk, bq, causal, window, share):
    assert A.score_block_share(Sq, Sk, bq, causal, window) == pytest.approx(share)


@pytest.mark.parametrize("Sq,bq", [(64, 128), (512, 128), (1500, 256),
                                   (2048, 256), (4096, 512), (8192, 512),
                                   (32768, 512)])
def test_default_q_block(Sq, bq):
    """An eighth of the sequence in whole 128-row tiles, at most 512 rows;
    the rows fall into at most eight spans of whole blocks."""
    assert A.default_q_block(Sq) == bq
    spans = A._block_spans(Sq, Sq, bq, True, 0)
    assert len(spans) <= 8
    assert [s[0] for s in spans[1:]] == [s[1] for s in spans[:-1]]
    assert spans[0][0] == 0 and spans[-1][1] == Sq
    assert all(q0 % bq == 0 for q0, *_ in spans)


@pytest.mark.parametrize("S", [2048, 4096, 8192, 32768])
def test_unrolled_blocks_stay_few(S):
    """The traced core holds at most eight spans' worth of score and PV
    products, however long the sequence: the blocks of a longer span are
    mapped, not unrolled, which bounds the program the compiler sees."""
    x = jax.ShapeDtypeStruct((1, S, 16, 128), jnp.bfloat16)
    core = functools.partial(A.attention_core, causal=True, remat=False)
    assert _count(jax.make_jaxpr(core)(x, x, x).jaxpr, ("dot_general",)) <= 16


def test_block_spans_cover_every_visible_key():
    """Every (row, key) pair the mask admits lies inside its block's span."""
    for Sq, Sk, bq, causal, window in [(64, 64, 16, True, 20),
                                       (56, 56, 16, True, 24),
                                       (64, 64, 16, False, 20),
                                       (40, 24, 16, False, 0),
                                       (200, 200, 16, True, 40),
                                       (200, 72, 16, False, 0)]:
        rows, keys = np.arange(Sq)[:, None], np.arange(Sk)[None, :]
        visible = np.ones((Sq, Sk), bool)
        if causal:
            visible &= keys <= rows
        if window > 0:
            visible &= rows - keys < window
        covered = np.zeros((Sq, Sk), bool)
        for q0, q1, lo, hi in A._block_spans(Sq, Sk, bq, causal, window):
            covered[q0:q1, lo:hi] = True
        assert not (visible & ~covered).any()
