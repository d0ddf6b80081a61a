"""Microbenchmarks for the Pallas-kernel hot spots.

Two families:

* **Fused optimizer tails** — for every algorithm in ``ALGORITHMS``, the
  elementwise update tail compared two ways over a 4M-element leaf:

  - *unfused* (measured): the textbook per-op execution — each tree op its
    own dispatch with materialized intermediates, exactly the pre-engine
    ``optimizers.py`` sequence, including the coupled weight-decay pass
    every baseline runs in large-batch training.  Wall time is the sum of
    the measured per-pass times; the same passes give the host's effective
    elementwise memory bandwidth.
  - *fused* (roofline at measured bandwidth): the update-spec stage kernel
    reads its operands and writes its outputs in ONE HBM pass, so its
    memory-bound cost is (stage bytes) / (measured bandwidth).  CPU XLA
    cannot reproduce a multi-output single-pass loop (it emits one loop
    per output — see ``fused_stage_us_cpu`` in the JSON for the raw CPU
    stage wall time), so the projection at the *measured* bandwidth is the
    faithful stand-in for the TPU kernel, whose math is validated
    elementwise in interpret mode in tests/test_kernels.py.

  Reported as ``algo,unfused_us,fused_us,speedup`` plus per-variant HBM
  pass bytes (in units of the leaf size n).

* **Attention / mLSTM reference paths** — CPU timings of the jnp chunked
  implementations (name,us_per_call,derived GB/s|GF/s), unchanged.

``run(json_path=...)`` additionally dumps the machine-readable per-algorithm
table (see benchmarks/run.py, which writes BENCH_kernels.json) so the perf
trajectory is tracked across PRs.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PpermuteChannel, build_topology
from repro.core.optimizers import ALGORITHMS, OptimizerConfig, make_optimizer
from repro.core.planes import PlaneLayout, plane_scalars
from repro.core.update_spec import (
    post_io,
    pre_io,
    reference_stage,
    run_update,
    stage_plan,
    update_spec,
)
from repro.kernels.flash_attention.ref import reference_attention  # noqa: F401 — table reference
from repro.kernels.fused_update import make_plane_stage
from repro.kernels.mlstm_chunk.ops import mlstm
from repro.launch.costmodel import count_primitive
from repro.models.attention import attention_core

N_TAIL = 4_000_000  # 16 MB fp32 per operand: memory-bound territory
BETA, WD, LR = 0.9, 0.01, 0.01


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):  # best-of-3 medians to tame CI-runner noise
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)  # us
    return best


# ---------------------------------------------------------------------------
# per-algorithm fused vs unfused tails
# ---------------------------------------------------------------------------

# one jit per elementary tree op == one dispatch + materialized output,
# exactly the pre-engine optimizer execution
_wd_pass = jax.jit(lambda w, x, g: w * x + g)
_mom = jax.jit(lambda b, m, g: b * m + g)
_step = jax.jit(lambda x, lr, d: x - lr * d)
_gt = jax.jit(lambda x, mix, lr: (x - mix) / jnp.maximum(lr, 1e-12))
_qg_m = jax.jit(
    lambda b, m, x, mix, lr: b * m + (1.0 - b) * (x - mix) / jnp.maximum(lr, 1e-12)
)
_d2_z = jax.jit(lambda x, xp, m, mp, lr: 2.0 * x - xp - lr * (m - mp))
_lars_scale = jax.jit(lambda r, g: r * g)
_sa_blend = jax.jit(lambda sg, gt, g: sg * gt + (1.0 - sg) * g)
_sa_apply = jax.jit(
    lambda x, lr, sg, b, m, gt: x - lr * (sg * b * m + gt)
)


def _unfused_tail_fns(algo):
    """The per-op sequence of the stock (pre-engine) optimizer step,
    communication excluded.  Every entry is one dispatch/HBM pass,
    annotated with the number of n-sized arrays it touches (reads+writes).
    """
    wd = (lambda e: _wd_pass(e["wd"], e["x"], e["g"]), 3)
    mom = (lambda e: _mom(e["beta"], e["m"], e["g"]), 3)
    step_m = (lambda e: _step(e["x"], e["lr"], e["m"]), 3)
    step_g = (lambda e: _step(e["x"], e["lr"], e["g"]), 3)
    awc_x = (lambda e: _step(e["mix"], e["lr"], e["m"]), 3)
    gt = (lambda e: _gt(e["x"], e["mix"], e["lr"]), 3)
    qg_m = (lambda e: _qg_m(e["beta"], e["m"], e["x"], e["mix"], e["lr"]), 4)
    d2_z = (lambda e: _d2_z(e["x"], e["xp"], e["m"], e["mp"], e["lr"]), 5)
    lars = (lambda e: _lars_scale(e["lr"], e["g"]), 2)  # r*g; norms excluded both ways
    # gt stands in via a distinct buffer (mix): aliasing g would let XLA
    # load it once and undercount the unfused baseline's memory traffic
    sa_blend = (lambda e: _sa_blend(e["sg"], e["mix"], e["g"]), 3)
    sa_apply = (lambda e: _sa_apply(e["x"], e["lr"], e["sg"], e["beta"], e["m"], e["g"]), 4)
    return {
        "pmsgd": [wd, mom, step_m],
        "pmsgd-lars": [wd, lars, mom, step_m],
        "dsgd": [wd, step_g],
        "dmsgd": [wd, mom, step_m],
        "da-dmsgd": [wd, mom, step_m],
        "awc-dmsgd": [wd, mom, awc_x],
        "slowmo": [wd, mom, step_m],  # periodic outer sync excluded
        "qg-dmsgd": [wd, mom, step_m, qg_m],
        "d2-dmsgd": [wd, mom, d2_z],
        "decentlam": [wd, step_g, gt, mom, step_m],
        # + per-gap damping: blend the momentum estimator, damp the applied
        # momentum (two extra dispatches the fused stage absorbs)
        "decentlam-sa": [wd, step_g, gt, sa_blend, mom, sa_apply],
    }[algo]


def _fused_stages(cfg):
    """(jitted stage callable, arrays touched) per engine stage, comm
    excluded.  The stage list comes from ``update_spec.stage_plan`` — the
    same gating ``run_update`` executes (free assigns skipped, decoupled-wd
    placement) — so the benchmark can't drift from the engine.

    The callable is the pure-jnp stage under one jit — CPU XLA runs one
    loop per *output*, so its wall time overstates the one-pass Pallas
    kernel; it is reported raw in the JSON while the headline fused cost
    is the arrays-touched roofline at measured bandwidth.
    """
    stages = []
    for kind, op, ctx in stage_plan(cfg):
        ins, outs = pre_io(op, ctx) if kind == "pre" else post_io(op)

        def stage_fn(env, _kind=kind, _op=op, _ctx=ctx, _ins=ins):
            ops = {n: {"w": env[n]} for n in _ins}
            s = {"lr": env["lr"], "gs": None, "r": None}
            return reference_stage(_kind, _op, _ctx, ops, s, {"w": env["x"]})

        stages.append((jax.jit(stage_fn), len(ins) + len(outs)))
    return stages


def bench_optimizer_tails(n=N_TAIL, iters=5):
    rng = np.random.default_rng(0)

    def arr():
        return jnp.asarray(rng.standard_normal(n), jnp.float32)

    env = {
        "x": arr(), "g": arr(), "m": arr(), "mix": arr(),
        "xp": arr(), "mp": arr(), "x_prev": None, "m_prev": None,
        "lr": jnp.float32(LR), "beta": jnp.float32(BETA), "wd": jnp.float32(WD),
        "sg": jnp.float32(0.5),
    }
    env["x_prev"], env["m_prev"] = env["xp"], env["mp"]

    table = {}
    for algo in ALGORITHMS:
        cfg = OptimizerConfig(algorithm=algo, momentum=BETA, weight_decay=WD)
        unfused = _unfused_tail_fns(algo)
        pass_times = [_time(f, env, iters=iters) for f, _ in unfused]
        t_unfused = sum(pass_times)
        unfused_arrays = sum(k for _, k in unfused)
        # effective elementwise bandwidth of this host, from the same passes
        bws = [k * 4.0 * n / t for (_, k), t in zip(unfused, pass_times)]
        bw = float(np.median(bws))  # bytes/us

        stages = _fused_stages(cfg)
        fused_arrays = sum(k for _, k in stages)
        t_fused = fused_arrays * 4.0 * n / bw  # one-pass roofline
        t_fused_cpu = sum(_time(f, env, iters=iters) for f, _ in stages)
        table[algo] = {
            "unfused_us": round(t_unfused, 1),
            "fused_us": round(t_fused, 1),
            "speedup": round(t_unfused / t_fused, 3),
            "unfused_passes": len(unfused),
            "fused_stages": len(stages),
            "unfused_array_passes": unfused_arrays,
            "fused_array_passes": fused_arrays,
            "fused_stage_us_cpu": round(t_fused_cpu, 1),
            "bandwidth_gb_s": round(bw * 1e6 / 1e9, 2),
            "elements": n,
        }
    return table


# ---------------------------------------------------------------------------
# tree-shaped workload: flat-plane path vs per-leaf path
# ---------------------------------------------------------------------------
#
# The 4M-element blob above measures per-pass bandwidth; real models are
# *trees* — many leaves, most small.  The plane update packs them into
# dtype buckets, so it pays one kernel launch per bucket per stage and one
# collective per bucket per edge class, whatever the number of leaves.
# This workload is a realistic transformer pytree (mixed bf16 matmul
# weights + f32 norm scales, per-layer q/k norms), counting:
#
# * launches/step     — pallas_call count in the traced jaxpr
#                       (buckets x stages)
# * collectives/step  — the ppermute-path analytic count (cross-checked
#                       against jaxpr-counted ppermutes in
#                       tests/scripts/distributed_equivalence.py)

TREE_N_NODES = 4
TREE_LAYERS = 48
TREE_D = 32


def _tree_template(n_layers=TREE_LAYERS, d=TREE_D, vocab=512):
    rng = np.random.default_rng(3)

    def arr(shape, dt):
        return jnp.asarray(rng.standard_normal(shape), dt)

    tree = {"embed": {"table": arr((vocab, d), jnp.bfloat16)},
            "final_ln": {"scale": arr((d,), jnp.float32)}}
    for i in range(n_layers):
        tree[f"layer_{i:02d}"] = {
            "qkv": arr((d, 3 * d), jnp.bfloat16),
            "o": arr((d, d), jnp.bfloat16),
            "up": arr((d, 4 * d), jnp.bfloat16),
            "down": arr((4 * d, d), jnp.bfloat16),
            "ln1": arr((d,), jnp.float32),
            "ln2": arr((d,), jnp.float32),
            "q_norm": arr((d,), jnp.float32),
            "k_norm": arr((d,), jnp.float32),
        }
    return tree


def _tree_counts(cfg, template) -> dict[str, int]:
    """Static launch counts of one plane update tail, from the jaxpr.

    Uses the per-node layout with an identity-closure transport so the
    trace carries only the engine's own launches; ``pallas_call``
    occurrences are counted recursively (``interpret=True`` lowers through
    the same primitive the TPU path uses).
    """
    spec = update_spec(cfg)
    layout = PlaneLayout.build(template)
    x = template
    g = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), template)
    state = make_optimizer(cfg).init(x)

    def gossip(tree, step, comp):
        return tree, comp

    def mean(tree):
        return tree

    kw = dict(lr=0.01, step_idx=jnp.int32(0), gossip=gossip, mean=mean,
              comp_state=())

    def plane_fn(x, g, state):
        xp = layout.pack(x)
        gp = layout.pack(g, dtype=jnp.float32)
        sp = {k: layout.pack(v, dtype=jnp.float32) for k, v in state.items()}
        return run_update(spec, cfg, x=xp, g=gp, state=sp,
                          stage=make_plane_stage("pallas_interpret"),
                          scalars=plane_scalars(cfg, layout, x, g), **kw)

    return {
        "launches_plane": count_primitive(
            jax.make_jaxpr(plane_fn)(x, g, state), "pallas_call"
        ),
        "stages": len(stage_plan(cfg)),
        "n_leaves": len(jax.tree.leaves(template)),
        "n_buckets": len(layout.segments),
    }


def bench_tree_workload():
    template = _tree_template()
    topo = build_topology("ring", TREE_N_NODES)
    # collectives accounting uses the distributed wire path's analytic count
    wire = PpermuteChannel(topo, "data")
    layout = PlaneLayout.build(template)
    plane_payload = layout.plane_shapes(jnp.float32)

    table: dict[str, dict] = {}
    for algo in ALGORITHMS:
        cfg = OptimizerConfig(algorithm=algo, momentum=BETA, weight_decay=WD)
        spec = update_spec(cfg)
        entry = dict(_tree_counts(cfg, template))
        entry["gossips_per_step"] = spec.gossips_per_step
        entry["collectives_plane"] = (
            wire.collectives_per_round(plane_payload) * spec.gossips_per_step
        )
        table[algo] = entry

    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(template))
    return {
        "n_nodes": TREE_N_NODES,
        "topology": "ring",
        "edge_classes": len(topo.edge_classes(0)),
        "n_params": n_params,
        "n_leaves": len(jax.tree.leaves(template)),
        "n_buckets": len(layout.segments),
        "per_algorithm": table,
    }


# ---------------------------------------------------------------------------
# sharded planes: per-rank launch/collective counts at tp > 1 vs tp == 1
# ---------------------------------------------------------------------------
#
# The tentpole claim of the sharded-layout refactor: one mesh column of a
# tp-sharded plane layout runs the SAME program shape as the tp == 1
# collapse — O(buckets x stages) pallas_calls per rank, O(buckets x
# edge-classes) node-axis collectives per rank, and ZERO extra model-axis
# collectives per step (gossip ships per-rank local shards over the node
# axes only; nothing in the update tail reduces over the model axis).
# Counted from the traced jaxpr on the rank-local layout — the distributed
# tier cross-checks the same counts inside a real shard_map program
# (tests/scripts/distributed_equivalence.py, mode "planes-tp").

TP_SHARDED = 2
TP_ALGOS = ("decentlam", "decentlam-sa")


def _tree_shardings(template):
    """PartitionSpecs for ``_tree_template``: megatron-style column/row
    splits on the matmul weights + vocab-sharded embedding; norm scales
    replicated (their dims don't divide, and they're tiny)."""
    from jax.sharding import PartitionSpec as P

    specs = {"embed": {"table": P("model", None)},
             "final_ln": {"scale": None}}
    for key in template:
        if not key.startswith("layer_"):
            continue
        specs[key] = {
            "qkv": P(None, "model"), "o": P("model", None),
            "up": P(None, "model"), "down": P("model", None),
            "ln1": None, "ln2": None, "q_norm": None, "k_norm": None,
        }
    return specs


def bench_tp_sharded(tp: int = TP_SHARDED):
    template = _tree_template()
    specs = _tree_shardings(template)
    topo = build_topology("ring", TREE_N_NODES)
    wire = PpermuteChannel(topo, "data")

    out: dict = {
        "tp": tp,
        # analytic model-axis budget: the sharded plane step adds no
        # collectives over the model axis (checked: the jaxpr-counted
        # launches below come from the SAME rank-local program per column)
        "model_axis_collectives_per_step": 0,
        "per_algorithm": {},
    }
    for algo in TP_ALGOS:
        cfg = OptimizerConfig(algorithm=algo, momentum=BETA, weight_decay=WD)
        spec = update_spec(cfg)
        entry: dict = {"stages": len(stage_plan(cfg))}
        for label_tp in (1, tp):
            lay = (
                PlaneLayout.build(template) if label_tp == 1
                else PlaneLayout.build(template, tp=label_tp, shardings=specs)
            )
            local = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), lay.local_template()
            )
            g = jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.float32), local
            )
            state = make_optimizer(cfg).init(local)
            kw = dict(lr=0.01, step_idx=jnp.int32(0),
                      gossip=lambda t, s, c: (t, c), mean=lambda t: t,
                      comp_state=())

            def plane_fn(x, g, state, _lay=lay, _spec=spec, _cfg=cfg, _kw=kw):
                xp = _lay.pack(x)
                gp = _lay.pack(g, dtype=jnp.float32)
                sp = {k: _lay.pack(v, dtype=jnp.float32)
                      for k, v in state.items()}
                return run_update(
                    _spec, _cfg, x=xp, g=gp, state=sp,
                    stage=make_plane_stage("pallas_interpret"),
                    scalars=plane_scalars(_cfg, _lay, x, g), **_kw,
                )

            entry[f"launches_plane_tp{label_tp}"] = count_primitive(
                jax.make_jaxpr(plane_fn)(local, g, state), "pallas_call"
            )
            # node-axis wire cost per rank: local buckets only
            entry[f"collectives_plane_tp{label_tp}"] = (
                wire.collectives_per_round(lay.plane_shapes(jnp.float32))
                * spec.gossips_per_step
            )
            entry["n_buckets"] = len(lay.segments)
        out["per_algorithm"][algo] = entry
    return out


# ---------------------------------------------------------------------------
# attention / mlstm reference-path timings (unchanged hot spots)
# ---------------------------------------------------------------------------


def bench_kernel_refs():
    rng = np.random.default_rng(0)
    rows = []

    B, S, H, hd = 1, 1024, 4, 64
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    g = jax.jit(lambda q: attention_core(q, q, q, causal=True, q_block=256))
    us = _time(g, q)
    fl = 4 * B * H * S * S * hd / 2
    rows.append(("attention_core_1k", us, f"{fl/us/1e3:.1f}GF/s"))

    q2 = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    v2 = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    gates = jnp.asarray(rng.standard_normal((1, 2, 512)), jnp.float32)
    h = jax.jit(lambda a, b, c: mlstm(a, a, b, c, c + 2, chunk=128, impl="ref"))
    us = _time(h, q2, v2, gates)
    rows.append(("mlstm_chunk_512", us, ""))
    return rows


def run(csv: bool = True, json_path: str | None = None):
    tails = bench_optimizer_tails()
    tree = bench_tree_workload()
    tree["tp_sharded"] = bench_tp_sharded()
    refs = bench_kernel_refs()

    if csv:
        print(
            "algo,unfused_us,fused_us,speedup,"
            "unfused_array_passes,fused_array_passes"
        )
        for algo, row in tails.items():
            print(
                f"tail/{algo},{row['unfused_us']:.0f},{row['fused_us']:.0f},"
                f"{row['speedup']:.2f},{row['unfused_array_passes']},"
                f"{row['fused_array_passes']}"
            )
        print("algo,launches_plane,collectives_plane")
        for algo, row in tree["per_algorithm"].items():
            print(
                f"tree/{algo},{row['launches_plane']},"
                f"{row['collectives_plane']:.0f}"
            )
        tp = tree["tp_sharded"]["tp"]
        print(
            f"algo,launches_plane_tp1,launches_plane_tp{tp},"
            f"collectives_plane_tp1,collectives_plane_tp{tp}"
        )
        for algo, row in tree["tp_sharded"]["per_algorithm"].items():
            print(
                f"tp/{algo},{row['launches_plane_tp1']},"
                f"{row[f'launches_plane_tp{tp}']},"
                f"{row['collectives_plane_tp1']:.0f},"
                f"{row[f'collectives_plane_tp{tp}']:.0f}"
            )
        print("name,us_per_call,derived")
        for name, us, d in refs:
            print(f"kernel/{name},{us:.0f},{d}")

    payload = {
        "bench": "kernel_microbench",
        "config": {"n": N_TAIL, "beta": BETA, "weight_decay": WD, "lr": LR},
        "optimizer_tails": tails,
        "tree_workload": tree,
        "kernel_refs": [
            {"name": name, "us_per_call": round(us, 1), "derived": d}
            for name, us, d in refs
        ],
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {json_path}")
    return payload


if __name__ == "__main__":
    run()
