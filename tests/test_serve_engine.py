"""Continuous-batching scheduler: slot mechanics pinned against the
sequential per-request oracle.

* engine output (variable-length prompts, staggered submissions, queue
  deeper than the slot count) is **token-identical** to prefilling each
  request alone at its exact length and greedy-decoding sequentially;
* snapshot swaps between request waves: completions produced before an
  accepted publish use the old weights, completions after use the new —
  each side matching its own oracle — and the measured swap count is 1;
* an engine waiting on a gated publisher ticks without decoding until the
  first version ships;
* ``eos_id`` terminates a slot early at exactly the oracle's sequence;
* ``greedy_decode_loop`` unit semantics (token threading + position
  advance) on a synthetic decode_fn.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_lm
from repro.core.planes import PlaneLayout
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.layers import TPContext
from repro.serve import Request, ServeEngine, WeightPublisher, greedy_decode_loop

CFG = tiny_lm(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
              vocab_size=64)
RT = T.RuntimeConfig(dtype="float32", remat=False)
TP1 = TPContext(size=1)
MAX_PROMPT, MAX_NEW = 12, 6
TL = MAX_PROMPT + MAX_NEW


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


def _params(seed=0):
    return T.init_params(jax.random.key(seed), CFG, tp=1)


def _prompts(n, seed=0):
    r = np.random.default_rng(seed)
    return [
        r.integers(0, CFG.vocab_size, size=int(r.integers(2, MAX_PROMPT + 1)))
        .astype(np.int32)
        for _ in range(n)
    ]


def _oracle(params, prompt, n_steps):
    """Sequential reference: exact-length prefill, then greedy decode via
    the shared loop — re-feeding the last prompt token at its true position
    exactly like slot admission does."""
    n = prompt.size
    _, cache = jax.jit(
        lambda p, b: T.prefill(p, b, CFG, TP1, RT, target_len=TL)
    )(params, {"tokens": jnp.asarray(prompt[None, :])})
    decode_fn = jax.jit(
        lambda p, tok, c, t: T.decode_step(p, tok, c, t, CFG, TP1, RT,
                                           target_len=TL)
    )
    toks, _ = greedy_decode_loop(
        decode_fn, params, cache,
        jnp.asarray(prompt[None, -1:]), jnp.int32(n - 1), n_steps,
    )
    return np.asarray(toks[0])


def test_engine_matches_sequential_oracle():
    params = _params()
    prompts = _prompts(7, seed=1)
    eng = ServeEngine(CFG, _mesh(), slots=3, max_prompt=MAX_PROMPT,
                      max_new=MAX_NEW, runtime=RT, params=params)
    # staggered load: 4 up front, 3 more mid-flight, queue > slots
    for i in range(4):
        eng.submit(Request(rid=i, tokens=prompts[i], max_new_tokens=MAX_NEW))
    for _ in range(2):
        eng.tick()
    for i in range(4, 7):
        eng.submit(Request(rid=i, tokens=prompts[i], max_new_tokens=MAX_NEW))
    done = eng.run_until_drained()
    assert sorted(c.rid for c in done) == list(range(7))
    for c in done:
        np.testing.assert_array_equal(
            c.tokens, _oracle(params, prompts[c.rid], MAX_NEW), str(c.rid)
        )
        assert c.submitted_s <= c.admitted_s <= c.finished_s
    st = eng.stats()
    assert st["completed"] == 7 and st["swaps"] == 0
    assert st["prefills"] >= 2  # two admission waves at minimum
    assert eng.idle and not eng.tick()


def test_engine_snapshot_swap_between_waves():
    """Wave 1 runs on published v1, wave 2 on v2; each matches its own
    oracle and exactly one swap (v1 -> v2) is counted."""
    params_a, params_b = _params(0), _params(1)
    lay = PlaneLayout.build(params_a)
    pub = WeightPublisher(lay, gap_threshold=0, check_consistency=True)
    prompts = _prompts(4, seed=2)
    eng = ServeEngine(CFG, _mesh(), slots=2, max_prompt=MAX_PROMPT,
                      max_new=MAX_NEW, runtime=RT, publisher=pub)

    assert pub.offer(params_a, version=1, gap=0)
    for i in range(2):
        eng.submit(Request(rid=i, tokens=prompts[i], max_new_tokens=MAX_NEW))
    eng.run_until_drained()
    assert eng.version == 1

    assert pub.offer(params_b, version=2, gap=0)
    for i in range(2, 4):
        eng.submit(Request(rid=i, tokens=prompts[i], max_new_tokens=MAX_NEW))
    done = {c.rid: c for c in eng.run_until_drained()}

    assert eng.version == 2 and eng.stats()["swaps"] == 1
    for rid, ref in [(0, params_a), (1, params_a), (2, params_b), (3, params_b)]:
        np.testing.assert_array_equal(
            done[rid].tokens, _oracle(ref, prompts[rid], MAX_NEW), str(rid)
        )


def test_engine_waits_on_gated_publisher():
    """Before the consensus gate clears the first version, ticks are
    waiting ticks — no prefill, no decode; once it ships, the queue drains."""
    params = _params()
    lay = PlaneLayout.build(params)
    pub = WeightPublisher(lay, gap_threshold=0)
    prompt = _prompts(1, seed=3)[0]
    eng = ServeEngine(CFG, _mesh(), slots=2, max_prompt=MAX_PROMPT,
                      max_new=3, runtime=RT, publisher=pub)
    eng.submit(Request(rid=0, tokens=prompt, max_new_tokens=3))

    assert not pub.offer(params, version=1, gap=5)  # gate holds it back
    for _ in range(3):
        assert eng.tick()  # pending work, but nothing runnable
    assert eng.waiting_ticks == 3 and eng.decode_batches == 0

    assert pub.offer(params, version=2, gap=0)
    done = eng.run_until_drained()
    np.testing.assert_array_equal(done[0].tokens, _oracle(params, prompt, 3))


def test_engine_eos_early_exit():
    params = _params()
    prompt = _prompts(1, seed=4)[0]
    ref = _oracle(params, prompt, MAX_NEW)
    eos = int(ref[2])  # make the oracle's 3rd token (or earlier) the stop
    stop = int(np.argmax(ref == eos))  # first occurrence
    eng = ServeEngine(CFG, _mesh(), slots=2, max_prompt=MAX_PROMPT,
                      max_new=MAX_NEW, runtime=RT, params=params, eos_id=eos)
    eng.submit(Request(rid=0, tokens=prompt, max_new_tokens=MAX_NEW))
    done = eng.run_until_drained()
    np.testing.assert_array_equal(done[0].tokens, ref[: stop + 1])


def test_engine_rejects_oversized_requests():
    eng = ServeEngine(CFG, _mesh(), slots=1, max_prompt=4, max_new=2,
                      runtime=RT, params=_params())
    with pytest.raises(AssertionError):
        eng.submit(Request(rid=0, tokens=np.arange(5, dtype=np.int32),
                           max_new_tokens=1))
    with pytest.raises(AssertionError):
        eng.submit(Request(rid=0, tokens=np.arange(3, dtype=np.int32),
                           max_new_tokens=3))


def test_decode_per_slot_t_sinusoid_path():
    """The sinusoid (rope_theta=0) embed path takes (B,) positions: each
    slot of a heterogeneous-t batched decode matches its own scalar-t
    decode off a solo prefill."""
    import dataclasses

    cfg = dataclasses.replace(CFG, rope_theta=0.0)
    params = T.init_params(jax.random.key(0), cfg, tp=1)
    rng = np.random.default_rng(5)
    B, S = 3, 10
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)), jnp.int32)
    _, cache = T.prefill(params, {"tokens": toks[:, :S]}, cfg, TP1, RT,
                         target_len=TL)
    tvec = jnp.asarray([S, S - 2, S - 1], jnp.int32)
    lg, _ = T.decode_step(params, toks[:, S:S + 1], cache, tvec, cfg, TP1, RT,
                          target_len=TL)
    for i in range(B):
        n = int(tvec[i])
        _, ci = T.prefill(params, {"tokens": toks[i:i + 1, :n]}, cfg, TP1, RT,
                          target_len=TL)
        lg_i, _ = T.decode_step(params, toks[i:i + 1, S:S + 1], ci,
                                jnp.int32(n), cfg, TP1, RT, target_len=TL)
        np.testing.assert_allclose(
            np.asarray(lg[i]), np.asarray(lg_i[0]), atol=1e-4, rtol=1e-4
        )


def test_greedy_decode_loop_threads_tokens_and_positions():
    """Synthetic decode_fn whose argmax is ``(tok + t) % V``: the loop must
    feed each sampled token back and advance per-slot positions by one."""
    V = 11

    def decode_fn(params, tok, cache, t):
        nxt = (tok[:, 0] + t) % V
        return jax.nn.one_hot(nxt, V), cache

    first = jnp.asarray([[3], [7]], jnp.int32)
    t0 = jnp.asarray([2, 5], jnp.int32)
    toks, cache = greedy_decode_loop(decode_fn, None, "cache", first, t0, 4)
    assert cache == "cache"
    expect = np.zeros((2, 4), np.int32)
    cur, t = np.array([3, 7]), np.array([2, 5])
    for s in range(4):
        cur = (cur + t) % V
        expect[:, s] = cur
        t = t + 1
    np.testing.assert_array_equal(np.asarray(toks), expect)
