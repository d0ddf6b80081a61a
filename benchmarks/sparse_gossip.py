"""Row-sparse gossip comm volume -> ``BENCH_gossip.json``.

Four sections, all machine-readable (gated by
``tests/ci/check_bench_gossip.py`` in the dist CI tier):

* **scenarios** — the analytic per-neighbor-send comm volume of the
  row-sparse channel on real plane layouts, with dirty rows derived by the
  *actual* :class:`~repro.sparse.tracker.RowTracker` from concrete touch
  events (token ids + router hit masks), never hand-counted:

  - ``moe_concentrated`` (granite-moe-1b-a400m, full config): domain-
    concentrated routing — every layer's microbatch lands in the same
    ``top_k`` = 8 of 32 experts, 2048 unique tokens/step.  This is the
    gated headline: sparse int8-row bytes <= 10% of dense f32 bytes.
  - ``moe_uniform`` (same model): saturating routing — every expert hot.
    NOT gated (``gated: false``), reported so the concentration
    assumption behind the 10% claim is explicit: with uniform routing
    the expert slabs ship densely and only the embedding + int8-row
    savings remain.
  - ``embed_heavy`` (inline dense config, 100k vocab, d_model 256):
    untied input embeddings dominate.  The *output head stays dense*
    (softmax grads touch every vocab row), which bounds the sparsity
    saving at the input-table share — recorded, not hidden.

  Three ratios per scenario keep sparsity and compression honest:
  ``ratio_sparsity`` (sparse f32 / dense f32 — row shipping alone),
  ``ratio_compression`` (dense int8-row / dense f32 — quantization
  alone), and ``ratio_combined`` (sparse int8-row / dense f32 — the
  deployment config the gate reads).

* **claims.bit_exact_all_dirty** — re-measured, not asserted-by-fiat: for
  every algorithm, the sparse channel's trajectory with every row marked
  is compared bitwise against the dense channel's (exact + delta modes).

* **smoke_crosscheck** — the analytic row model vs the channel's *measured*
  volume counters on the granite SMOKE plane layout: the same masks the
  scenario table uses are pushed through ``SparseStackedChannel.apply``
  and the accounted egress must match the analytic prediction to rtol
  1e-6 (a divergence means the byte accounting regressed).

* **sim_crosscheck** — the cluster simulator with row-sparse gossip on
  row-supported gradients vs the dense reference: max trajectory error
  (exact tracking => equal up to per-program FMA contraction) and the
  wire savings the sim's own counters report.

Emits CSV rows ``scenario,dense_f32_mb,sparse_f32_mb,sparse_int8_mb,
ratio_sparsity,ratio_combined`` for the human-readable run log.
"""

from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import (
    OptimizerConfig,
    StackedChannel,
    build_topology,
    make_linear_regression,
    make_optimizer,
    make_stacked_mean,
    wire_bytes,
)
from repro.core.optimizers import ALGORITHMS
from repro.core.planes import LANES
from repro.models import transformer as T
from repro.sparse import RowTracker, SparseStackedChannel, grad_row_masks
from repro.train.train_state import model_plane_layout

ROW_F32 = 4.0 * LANES  # one plane row's fp32 payload bytes


def _row_wire(comp: str | None) -> float:
    """Wire bytes of one shipped plane row: payload + i32 row index."""
    return wire_bytes(ROW_F32, comp) + 4.0


def _tracker_for(cfg):
    layout = model_plane_layout(cfg, 1)
    tmpl = jax.eval_shape(lambda k: T.init_params(k, cfg, 1), jax.random.key(0))
    return layout, RowTracker.for_model(
        layout, tmpl, tied_embeddings=cfg.tie_embeddings
    )


def _sparse_bytes(layout, masks, comp: str | None) -> float:
    """Per-neighbor-send bytes of the row-sparse framing (the channel's own
    model: shipped rows x (row wire + index), capped at the bucket's dense
    wire)."""
    total = 0.0
    for key, rows in layout.rows.items():
        dirty = int(np.asarray(masks[key]).sum())
        total += min(dirty * _row_wire(comp), wire_bytes(ROW_F32 * rows, comp))
    return total


def _dense_bytes(layout, comp: str | None) -> float:
    return sum(
        wire_bytes(ROW_F32 * rows, comp) for rows in layout.rows.values()
    )


def _scenario_masks(cfg, tracker, *, hot_experts, unique_tokens, seed=0):
    """Touch events -> row masks via the real tracker (no hand counting)."""
    rng = np.random.default_rng(seed)
    units: dict[str, np.ndarray] = {}
    for src in tracker.sources:
        if src.kind == "embed":
            units[src.name] = rng.choice(
                cfg.vocab_size, size=min(unique_tokens, cfg.vocab_size),
                replace=False,
            ).astype(np.int32)
        elif src.kind == "moe":
            lg = src.units // cfg.n_experts
            hot = np.zeros((lg, cfg.n_experts), bool)
            hot[:, rng.choice(cfg.n_experts, size=hot_experts, replace=False)] = True
            units[src.name] = hot
    return tracker.step_masks(units)


def _scenario(cfg, *, hot_experts, unique_tokens, gated, note):
    layout, tracker = _tracker_for(cfg)
    masks = _scenario_masks(
        cfg, tracker, hot_experts=hot_experts, unique_tokens=unique_tokens
    )
    dense_f32 = _dense_bytes(layout, None)
    entry = {
        "model": cfg.name,
        "gated": gated,
        "note": note,
        "hot_experts": hot_experts,
        "n_experts": cfg.n_experts,
        "unique_tokens": unique_tokens,
        "vocab_size": cfg.vocab_size,
        "rows_total": int(sum(layout.rows.values())),
        "rows_dirty": int(
            sum(int(np.asarray(m).sum()) for m in masks.values())
        ),
        "dense_f32_bytes": dense_f32,
        "sparse_f32_bytes": _sparse_bytes(layout, masks, None),
        "dense_int8row_bytes": _dense_bytes(layout, "int8-row"),
        "sparse_int8row_bytes": _sparse_bytes(layout, masks, "int8-row"),
        "tracker": tracker.summary(),
    }
    entry["ratio_sparsity"] = entry["sparse_f32_bytes"] / dense_f32
    entry["ratio_compression"] = entry["dense_int8row_bytes"] / dense_f32
    entry["ratio_combined"] = entry["sparse_int8row_bytes"] / dense_f32
    return entry


def _bit_exact_claims() -> dict:
    """All-dirty sparse vs dense, bitwise, every algorithm x both modes."""
    topo = build_topology("ring", 4)
    prob = make_linear_regression(n=4, m=6, d=5, noise=0.01, seed=3)
    rng = np.random.default_rng(3)
    x0 = jnp.asarray(
        np.broadcast_to(rng.standard_normal((1, prob.dim)), (4, prob.dim)),
        jnp.float32,
    )
    mean = make_stacked_mean(4)

    def run(opt, channel):
        params, s, ch = x0, opt.init(x0), channel.init(x0)
        for k in range(4):
            g = prob.grad(params)
            if hasattr(channel, "mark"):
                ch = channel.mark(ch, grad_row_masks(g))
            params, s, ch = opt.step(
                params, g, s, lr=jnp.float32(1e-2), step_idx=jnp.int32(k),
                gossip=channel, mean=mean, comp_state=ch,
            )
        return np.asarray(params)

    claims = {}
    for mode in ("exact", "delta"):
        ok = True
        for algorithm in ALGORITHMS:
            opt = make_optimizer(
                OptimizerConfig(algorithm=algorithm, momentum=0.8)
            )
            dense = run(opt, StackedChannel(topo))
            sparse = run(opt, SparseStackedChannel(
                topo, mode=mode, calls_per_step=opt.gossips_per_step
            ))
            ok &= bool(np.array_equal(dense, sparse))
        claims[mode] = {"bit_exact": ok, "algorithms": len(ALGORITHMS)}
    return claims


def _smoke_crosscheck() -> dict:
    """Measured channel counters vs the analytic row model, granite SMOKE."""
    cfg = get_config("granite-moe-1b-a400m", smoke=True)
    layout, tracker = _tracker_for(cfg)
    masks = _scenario_masks(
        cfg, tracker, hot_experts=cfg.top_k, unique_tokens=32
    )
    n, steps = 4, 3
    topo = build_topology("ring", n)
    channel = SparseStackedChannel(topo)
    rng = np.random.default_rng(7)
    x = {
        key: jnp.asarray(
            rng.standard_normal((n, rows, LANES)), jnp.float32
        )
        for key, rows in layout.rows.items()
    }
    state = channel.init(x)
    for k in range(steps):
        state = channel.mark(state, masks)
        state, x = channel.apply(state, x, jnp.int32(k))
    vol = state["rows"]["vol"]
    sends = float(np.mean(
        [len(topo.edge_classes(t)) for t in range(topo.period)]
    ))
    measured = {
        "sparse": float(np.mean(np.asarray(vol["sparse"]))) / steps,
        "dense": float(np.mean(np.asarray(vol["dense"]))) / steps,
    }
    analytic = {
        "sparse": sends * _sparse_bytes(layout, masks, None),
        "dense": sends * _dense_bytes(layout, None),
    }
    err = max(
        abs(measured[k] - analytic[k]) / analytic[k] for k in measured
    )
    return {
        "model": cfg.name,
        "sends_per_step": sends,
        "measured_bytes_per_step": measured,
        "analytic_bytes_per_step": analytic,
        "rel_err": err,
        "ok": err <= 1e-6,
    }


def _sim_crosscheck() -> dict:
    """Simulator with row-sparse gossip vs the dense reference."""
    from repro.sim import SimSpec, simulate

    n, d = 8, 12
    key = jax.random.key(0)
    A = jax.random.normal(key, (n, d, d)) * 0.1 + jnp.eye(d)
    b = jax.random.normal(jax.random.key(1), (n, d))

    def grads(params, step):
        g = jnp.einsum("nij,nj->ni", A, params) - b
        rows = (jnp.arange(d)[None, :] + jnp.asarray(step)) % 3 == 0
        return jnp.where(rows, g, 0.0)

    opt = make_optimizer(OptimizerConfig(algorithm="decentlam", momentum=0.8))
    x0 = jnp.zeros((n, d), jnp.float32)

    def run(sparse):
        spec = SimSpec(topology="ring", n=n, lr=1e-2, n_steps=12, seed=0,
                       sparse=sparse)
        return simulate(opt, spec, x0, grads)

    rd, rs = run(None), run("exact")
    err = float(np.max(np.abs(np.asarray(rd.params) - np.asarray(rs.params))))
    return {
        "algorithm": "decentlam",
        "max_param_err": err,
        "wire_sparse_bytes": rs.comm["wire_sparse_bytes"],
        "wire_dense_bytes": rs.comm["wire_dense_bytes"],
        "ok": err <= 1e-5
        and rs.comm["wire_sparse_bytes"] < rs.comm["wire_dense_bytes"],
    }


def run(json_path: str = "BENCH_gossip.json") -> None:
    granite = get_config("granite-moe-1b-a400m")
    embed_heavy = dataclasses.replace(
        get_config("qwen3-0.6b"),
        name="embed-heavy-dense",
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, d_ff=512,
        vocab_size=100352, qk_norm=False, head_dim=0, tie_embeddings=False,
    )
    scenarios = {
        "moe_concentrated": _scenario(
            granite,
            hot_experts=granite.top_k, unique_tokens=2048, gated=True,
            note="domain-concentrated routing: every layer's step lands in "
                 "the same top_k experts; the <= 10% gate assumes this",
        ),
        "moe_uniform": _scenario(
            granite,
            hot_experts=granite.n_experts, unique_tokens=2048, gated=False,
            note="saturating routing: every expert hot, expert slabs ship "
                 "densely — only embedding + int8-row savings remain "
                 "(reported so the concentration assumption is explicit)",
        ),
        "embed_heavy": _scenario(
            embed_heavy,
            hot_experts=0, unique_tokens=1024, gated=False,
            note="untied input embeddings dominate; the output head stays "
                 "dense (softmax grads are vocab-dense), bounding the "
                 "saving at the input-table share",
        ),
    }
    bench = {
        "config": {
            "lanes": LANES,
            "row_index_bytes": 4,
            "sparse_compression": "int8-row",
            "dense_baseline": "f32",
        },
        "scenarios": scenarios,
        "claims": {"bit_exact_all_dirty": _bit_exact_claims()},
        "smoke_crosscheck": _smoke_crosscheck(),
        "sim_crosscheck": _sim_crosscheck(),
    }
    with open(json_path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)

    print("scenario,dense_f32_mb,sparse_f32_mb,sparse_int8_mb,"
          "ratio_sparsity,ratio_combined")
    for name, s in scenarios.items():
        print(f"{name},{s['dense_f32_bytes']/1e6:.1f},"
              f"{s['sparse_f32_bytes']/1e6:.1f},"
              f"{s['sparse_int8row_bytes']/1e6:.1f},"
              f"{s['ratio_sparsity']:.3f},{s['ratio_combined']:.3f}")
    bx = bench["claims"]["bit_exact_all_dirty"]
    print(f"bit_exact_all_dirty,exact={bx['exact']['bit_exact']},"
          f"delta={bx['delta']['bit_exact']}")
    print(f"smoke_crosscheck,rel_err={bench['smoke_crosscheck']['rel_err']:.2e},"
          f"ok={bench['smoke_crosscheck']['ok']}")
    print(f"sim_crosscheck,max_param_err="
          f"{bench['sim_crosscheck']['max_param_err']:.2e},"
          f"ok={bench['sim_crosscheck']['ok']}")
    print(f"# wrote {json_path}")


if __name__ == "__main__":
    run()
