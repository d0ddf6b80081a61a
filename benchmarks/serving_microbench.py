"""Serving benchmark: request throughput, publication handoff, consensus gate.

Three sections (CPU wall-clock; shapes scaled so the *paths* — cache
build, rolling buffers, continuous batching, plane-snapshot handoff — are
exercised, not the hardware):

* ``paths`` — raw prefill latency and single-stream decode tokens/s (the
  original microbench, decode loop now driven by the shared
  :func:`repro.serve.greedy_decode_loop`);
* ``throughput`` — the continuous-batching :class:`~repro.serve.ServeEngine`
  under concurrent load fed by a :class:`~repro.serve.WeightPublisher`,
  with a weight version published **mid-load**: requests/s, generated
  tok/s, p50/p95 request latency, snapshot-swap count and stall time;
* ``handoff`` — plane-snapshot publication cost (host_pack / zero-copy
  view_unpack / full unpack) and the bit-exactness contract;
* ``publish_gate`` — publish rate vs gap threshold on a stale-gossip
  fleet (ring, delayed edges incident to node 0, gaps from
  :func:`repro.core.gossip.fleet_node_gaps`).

Emits CSV rows (``name,value,derived``) and, with ``json_path``, the
machine-readable ``BENCH_serve.json`` gated by
``tests/ci/check_bench_serve.py``.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import tiny_lm
from repro.core import build_topology
from repro.core.gossip import DelayedStackedChannel, fleet_node_gaps
from repro.core.planes import PlaneLayout
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.layers import TPContext
from repro.serve import (
    Request,
    ServeEngine,
    WeightPublisher,
    greedy_decode_loop,
    greedy_token,
)

TP1 = TPContext(size=1)


def _bench_paths(out: dict) -> list[tuple]:
    """Raw prefill + single-stream decode timings (the original rows)."""
    cfg = tiny_lm(n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, d_ff=1024,
                  vocab_size=8192)
    rt = T.RuntimeConfig(dtype="float32", remat=False, decode_grouped_gqa=True)
    params = T.init_params(jax.random.key(0), cfg, tp=1)
    rng = np.random.default_rng(0)
    B, PROMPT, GEN = 4, 256, 32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, PROMPT)), jnp.int32)

    prefill = jax.jit(
        lambda p, b: T.prefill(p, b, cfg, TP1, rt, target_len=PROMPT + GEN)
    )
    decode = jax.jit(
        lambda p, t, c, tt: T.decode_step(
            p, t, c, tt, cfg, TP1, rt, target_len=PROMPT + GEN
        )
    )

    logits, cache = prefill(params, {"tokens": toks})
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": toks})
    jax.block_until_ready(logits)
    t_prefill = (time.perf_counter() - t0) * 1e6

    first = greedy_token(logits)[:, None]  # prefill returns (B, V) last-token logits
    # warm the decode step, then time the shared greedy loop
    jax.block_until_ready(decode(params, first, cache, jnp.int32(PROMPT))[0])
    t0 = time.perf_counter()
    gen, _ = greedy_decode_loop(decode, params, cache, first,
                                jnp.int32(PROMPT), GEN)
    jax.block_until_ready(gen)
    t_decode = (time.perf_counter() - t0) / GEN * 1e6

    out["paths"] = {
        "prefill_us": t_prefill,
        "decode_step_us": t_decode,
        "prefill_tok_per_s": B * PROMPT / t_prefill * 1e6,
        "decode_tok_per_s": B / t_decode * 1e6,
    }
    return [
        ("serve/prefill_256x4", t_prefill, f"{B*PROMPT/t_prefill*1e6:.0f}tok/s"),
        ("serve/decode_step", t_decode, f"{B/t_decode*1e6:.0f}tok/s"),
    ]


def _bench_throughput(out: dict) -> list[tuple]:
    """ServeEngine under concurrent load with a mid-load weight publish."""
    cfg = tiny_lm(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                  vocab_size=256)
    rt = T.RuntimeConfig(dtype="float32", remat=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init_params(jax.random.key(0), cfg, tp=1)
    params2 = jax.tree.map(lambda x: x * 1.01, params)
    lay = PlaneLayout.build(params)
    pub = WeightPublisher(lay, gap_threshold=0, check_consistency=True)
    pub.offer(params, version=1, gap=0)

    SLOTS, MAX_PROMPT, MAX_NEW, N_REQ = 4, 32, 16, 12
    eng = ServeEngine(cfg, mesh, slots=SLOTS, max_prompt=MAX_PROMPT,
                      max_new=MAX_NEW, runtime=rt, publisher=pub)
    rng = np.random.default_rng(1)
    for i in range(N_REQ):
        n = int(rng.integers(4, MAX_PROMPT + 1))
        eng.submit(Request(
            rid=i,
            tokens=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=MAX_NEW,
        ))
    # warm the compiled steps outside the timed window
    eng.tick()

    t0 = time.perf_counter()
    published_mid = False
    while eng.tick():
        if not published_mid and len(eng.completions) >= N_REQ // 3:
            pub.offer(params2, version=2, gap=0)  # swap under live load
            published_mid = True
    wall = time.perf_counter() - t0

    done = eng.completions
    gen_tokens = int(sum(c.tokens.size for c in done))
    lat = np.sort([c.latency_s for c in done])
    st = eng.stats()
    out["throughput"] = {
        "slots": SLOTS,
        "requests": N_REQ,
        "completed": len(done),
        "generated_tokens": gen_tokens,
        "wall_s": wall,
        "tok_per_s": gen_tokens / wall,
        "requests_per_s": len(done) / wall,
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_p95_s": float(np.percentile(lat, 95)),
        "decode_batches": st["decode_batches"],
        "prefills": st["prefills"],
        "swaps": st["swaps"],
        "swap_stall_s": st["swap_stall_s"],
        "swap_stall_frac": st["swap_stall_s"] / wall,
        "final_version": st["version"],
    }
    tp = out["throughput"]
    return [
        ("serve/engine_tok_per_s", tp["tok_per_s"], f"{SLOTS}slots"),
        ("serve/engine_latency_p50", tp["latency_p50_s"] * 1e3, "ms"),
        ("serve/engine_latency_p95", tp["latency_p95_s"] * 1e3, "ms"),
        ("serve/engine_swap_stall", tp["swap_stall_s"] * 1e3,
         f"{tp['swaps']}swap"),
    ]


def _bench_handoff(out: dict) -> list[tuple]:
    """Plane-snapshot publication cost + the bit-exactness contract."""
    cfg = tiny_lm(n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, d_ff=1024,
                  vocab_size=8192)
    params = T.init_params(jax.random.key(0), cfg, tp=1)
    lay = PlaneLayout.build(params)
    nbytes = int(sum(np.asarray(x).nbytes for x in jax.tree.leaves(params)))

    def timeit(fn, reps=5):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps * 1e6

    planes = lay.host_pack(params)
    t_pack = timeit(lambda: lay.host_pack(params, out=planes))
    t_view = timeit(lambda: lay.view_unpack(planes))
    t_full = timeit(lambda: lay.unpack({k: v for k, v in planes.items()}))

    views = lay.view_unpack(planes)
    full = lay.unpack({k: np.asarray(v) for k, v in planes.items()})
    bit_exact = all(
        v.dtype == np.asarray(r).dtype and v.tobytes() == np.asarray(r).tobytes()
        for v, r in zip(jax.tree.leaves(views), jax.tree.leaves(full))
    )
    out["handoff"] = {
        "n_leaves": lay.n_leaves,
        "param_bytes": nbytes,
        "host_pack_us": t_pack,
        "view_unpack_us": t_view,
        "full_unpack_us": t_full,
        "view_speedup_vs_full": t_full / t_view,
        "bit_exact": bool(bit_exact),
    }
    return [
        ("serve/host_pack", t_pack, f"{nbytes/1e6:.1f}MB"),
        ("serve/view_unpack", t_view, f"{t_full/t_view:.1f}x_vs_full"),
    ]


def _bench_publish_gate(out: dict) -> list[tuple]:
    """Publish rate vs gap threshold on a stale-gossip ring: every edge
    incident to node 0 carries delay 3, so nodes 0/1/3 settle at consensus
    gap 3 while node 2 stays fresh."""
    n, delay, rounds = 4, 3, 12
    topo = build_topology("ring", n)
    D = np.zeros((n, n), int)
    for j in (1, 3):
        D[0, j] = D[j, 0] = delay
    tree = {"w": jnp.zeros((64,), jnp.float32)}
    lay = PlaneLayout.build(tree)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((n, 8)),
                    jnp.float32)

    sweep = []
    never_over = True
    for thr in range(delay + 1):
        ch = DelayedStackedChannel(topo, D)
        st = ch.init(x)
        pubs = [WeightPublisher(lay, gap_threshold=thr) for _ in range(n)]
        for t in range(rounds):
            st, _ = ch.apply(st, x, jnp.int32(t))
            gaps = fleet_node_gaps(ch, st)
            for i in range(n):
                if int(gaps[i]) > thr:
                    never_over &= not pubs[i].offer(
                        tree, version=t + 1, gap=int(gaps[i])
                    )
                else:
                    pubs[i].offer(tree, version=t + 1, gap=int(gaps[i]))
        sweep.append({
            "gap_threshold": thr,
            "per_node_publish_rate": [
                p.stats()["publish_rate"] for p in pubs
            ],
            "fresh_node_rate": pubs[2].stats()["publish_rate"],
            "stale_node_rate": pubs[0].stats()["publish_rate"],
        })
    out["publish_gate"] = {
        "topology": f"ring{n}",
        "delay": delay,
        "rounds": rounds,
        "stale_nodes": [0, 1, 3],
        "fresh_nodes": [2],
        "sweep": sweep,
        "stale_never_publish_over_threshold": bool(never_over),
    }
    return [
        (f"serve/publish_rate_thr{row['gap_threshold']}",
         row["stale_node_rate"],
         f"fresh={row['fresh_node_rate']:.2f}")
        for row in sweep
    ]


def run(csv: bool = True, json_path: str | None = None):
    out: dict = {}
    rows = []
    rows += _bench_paths(out)
    rows += _bench_throughput(out)
    rows += _bench_handoff(out)
    rows += _bench_publish_gate(out)
    if csv:
        print("name,value,derived")
        for name, v, d in rows:
            print(f"{name},{v:.2f},{d}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {json_path}")
    return rows


if __name__ == "__main__":
    run(json_path="BENCH_serve.json")
