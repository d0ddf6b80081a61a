"""CI gate for BENCH_kernels.json (fused-tail + flat-plane microbenchmarks).

Usage::

    python tests/ci/check_bench_kernels.py BENCH_kernels.json

Validates the machine-readable invariants the kernel subsystems promise
(ISSUE 1 + ISSUE 5 acceptance criteria):

* every algorithm's fused tail is projected no slower than the unfused
  per-op execution (``speedup >= 1.0`` — the roofline at measured
  bandwidth; a regression here means the stage plan grew redundant
  passes);
* the tree-shaped workload ran for every algorithm and its **launch
  counts are exactly structural**: the flat-plane update issues
  ``dtype-buckets x stages`` ``pallas_call``s — O(stages), independent of
  the tree — counted from the traced jaxpr, not estimated;
* collectives collapse the same way: ``buckets x edge-classes x gossips``
  (the analytic ppermute-path count; the distributed tier cross-checks it
  against jaxpr-counted ppermutes on a real mesh);
* the **sharded-plane** row (``tree_workload.tp_sharded``): one mesh
  column of a tp-sharded layout launches no more ``pallas_call``s than the
  tp == 1 collapse plus the model-axis collective budget — which must be
  0 (gossip ships per-rank local shards over the node axes only) — and
  its per-rank node-axis collective count matches tp == 1.

Exit code 1 on any violation.
"""

from __future__ import annotations

import json
import sys

MIN_FUSED_SPEEDUP = 1.0


def main() -> int:
    if len(sys.argv) < 2:
        print(__doc__)
        return 2
    with open(sys.argv[1]) as f:
        bench = json.load(f)

    errors: list[str] = []

    tails = bench.get("optimizer_tails", {})
    if not tails:
        errors.append("missing optimizer_tails")
    for algo, row in tails.items():
        if row.get("speedup", 0.0) < MIN_FUSED_SPEEDUP:
            errors.append(
                f"tails/{algo}: fused speedup {row.get('speedup')} < "
                f"{MIN_FUSED_SPEEDUP}"
            )

    tree = bench.get("tree_workload")
    if not tree:
        errors.append("missing tree_workload (flat-plane bench did not run)")
        tree = {}
    per_algo = tree.get("per_algorithm", {})
    n_buckets = tree.get("n_buckets", 0)
    classes = tree.get("edge_classes", 0)
    for algo in tails:
        if algo not in per_algo:
            errors.append(f"tree_workload: missing algorithm {algo!r}")
    for algo, row in per_algo.items():
        stages = row.get("stages", -1)
        if row.get("launches_plane") != n_buckets * stages:
            errors.append(
                f"tree/{algo}: plane launches {row.get('launches_plane')} != "
                f"buckets({n_buckets}) x stages({stages}) — the O(stages) "
                "claim regressed"
            )
        gossips = row.get("gossips_per_step", 0)
        if row.get("collectives_plane") != n_buckets * classes * gossips:
            errors.append(
                f"tree/{algo}: plane collectives {row.get('collectives_plane')}"
                f" != buckets({n_buckets}) x classes({classes}) x "
                f"gossips({gossips})"
            )

    tps = tree.get("tp_sharded")
    if not tps:
        errors.append(
            "missing tree_workload.tp_sharded (sharded-plane bench did not run)"
        )
    else:
        tp = tps.get("tp", 0)
        budget = tps.get("model_axis_collectives_per_step", -1)
        if budget != 0:
            errors.append(
                f"tp_sharded: model-axis collective budget is {budget}, "
                "expected 0 — the sharded plane step must not add "
                "model-axis collectives"
            )
        if not tps.get("per_algorithm"):
            errors.append("tp_sharded: no algorithms recorded")
        for algo, row in tps.get("per_algorithm", {}).items():
            l1 = row.get("launches_plane_tp1")
            lk = row.get(f"launches_plane_tp{tp}")
            if l1 is None or lk is None or lk > l1 + max(budget, 0):
                errors.append(
                    f"tp_sharded/{algo}: per-rank launches at tp={tp} ({lk}) "
                    f"exceed tp=1 ({l1}) + model-axis budget ({budget}) — "
                    "the per-rank O(buckets x stages) collapse regressed"
                )
            stages = row.get("stages", -1)
            nb = row.get("n_buckets", -1)
            if l1 != nb * stages:
                errors.append(
                    f"tp_sharded/{algo}: tp=1 launches {l1} != "
                    f"buckets({nb}) x stages({stages})"
                )
            if row.get(f"collectives_plane_tp{tp}") != row.get(
                "collectives_plane_tp1"
            ):
                errors.append(
                    f"tp_sharded/{algo}: per-rank node-axis collectives at "
                    f"tp={tp} ({row.get(f'collectives_plane_tp{tp}')}) != "
                    f"tp=1 ({row.get('collectives_plane_tp1')})"
                )

    if errors:
        print(f"KERNEL BENCH GATE: {len(errors)} violation(s):")
        for e in errors:
            print(f"  {e}")
        return 1
    print(
        f"KERNEL BENCH GATE: ok ({len(tails)} fused tails, "
        f"{len(per_algo)} tree rows, plane launches "
        f"O(stages) x {n_buckets} bucket(s), tp={tps.get('tp')} sharded "
        f"row per-rank launches == tp=1 with 0 model-axis collectives)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
