import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real jitted program (train_step for train
shapes, serve prefill/decode for the others) against the production mesh,
lowers it with ShapeDtypeStruct stand-ins (zero allocation), compiles it,
and records:

* ``compiled.memory_analysis()``  — per-device bytes (proves it fits),
* ``compiled.cost_analysis()``    — per-device FLOPs / bytes accessed,
* parsed collective egress bytes  — from the optimized HLO,
* the three roofline terms + dominant bottleneck (launch/roofline.py),
* MODEL_FLOPS / HLO_FLOPs utilization ratio.

Artifacts land in ``experiments/dryrun/<tag>/<mesh>/<arch>__<shape>.json``;
EXPERIMENTS.md §Dry-run / §Roofline are generated from them.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k --mesh pod1 --tag baseline
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --tag baseline
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, get_config, shape_applicable
from ..configs.base import ModelConfig, ShapeSpec
from ..core.optimizers import make_optimizer
from ..core.schedules import ScheduleConfig
from ..models import transformer as T
from ..train import serve as serve_mod
from ..train.step import TrainConfig, build_train_step
from ..train.train_state import abstract_train_state
from .costmodel import analyze_jaxpr
from .mesh import MODEL_AXIS, make_production_mesh, node_axes_of, n_nodes_of
from .roofline import model_flops, parse_collective_bytes, roofline_terms


def _abstract_batch(cfg: ModelConfig, shape: ShapeSpec, dtype=jnp.bfloat16):
    gb, s = shape.global_batch, shape.seq_len
    b = {
        "tokens": jax.ShapeDtypeStruct((gb, s), jnp.int32),
        "targets": jax.ShapeDtypeStruct((gb, s), jnp.int32),
    }
    if cfg.family == "vlm":
        b["patch_embeds"] = jax.ShapeDtypeStruct(
            (gb, cfg.num_patches, cfg.d_model), dtype
        )
    if cfg.arch_kind == "encdec":
        b["enc_frames"] = jax.ShapeDtypeStruct((gb, cfg.enc_seq, cfg.d_model), dtype)
    return b


def _abstract_serve_params(cfg: ModelConfig, tp: int, dtype=jnp.bfloat16):
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg, tp), jax.random.key(0))
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), shapes)


def _runtime(args) -> T.RuntimeConfig:
    return T.RuntimeConfig(
        dtype="bfloat16",
        attn_impl="jnp",  # Pallas kernels are TPU-target; CPU dry-run uses jnp
        remat=args.remat,
        remat_policy=args.remat_policy,
        decode_grouped_gqa=args.decode_grouped_gqa,
        mlstm_chunk=args.mlstm_chunk,
        ssm_chunk=args.ssm_chunk,
    )


def build_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, args):
    """Returns (lowered, meta) for one cell."""
    tp = mesh.shape[MODEL_AXIS]
    node_axes = node_axes_of(mesh)
    n_nodes = n_nodes_of(mesh)
    rt = _runtime(args)

    if shape.kind == "train":
        accum = args.grad_accum
        if accum == 0:  # auto: cap microbatch tokens per node at ~16k
            per_node_b = shape.global_batch // n_nodes
            want = max(1, per_node_b * shape.seq_len // 16384)
            accum = 1
            for c in range(1, per_node_b + 1):
                if per_node_b % c == 0 and c <= want:
                    accum = c
        tcfg = TrainConfig(
            algorithm=args.algorithm,
            topology=args.topology,
            gossip_impl=args.gossip_impl,
            compression=args.compression,
            grad_accum=accum,
            schedule=ScheduleConfig(kind="constant", peak_lr=1e-3),
            runtime=rt,
            fused_update=args.fused_update,
            gossip_serialize=args.gossip_serialize,
        )
        step, sspecs, bspecs, channel = build_train_step(
            cfg, tcfg, mesh, node_axes=node_axes, model_axis=MODEL_AXIS
        )
        opt = make_optimizer(tcfg.opt_config())
        state = abstract_train_state(cfg, opt, n_nodes, tp, channel)
        batch = _abstract_batch(cfg, shape)
        lowered = step.lower(state, batch)
        jx = jax.make_jaxpr(step)(state, batch)
        tokens = shape.global_batch * shape.seq_len
        return lowered, jx, {"training": True, "tokens": tokens,
                             "grad_accum": accum}

    scfg = serve_mod.ServeConfig(runtime=rt, target_len=shape.seq_len)
    params = _abstract_serve_params(cfg, tp)

    if shape.kind == "prefill":
        step, _ = serve_mod.build_prefill_step(
            cfg, mesh, scfg, global_batch=shape.global_batch,
            node_axes=node_axes, model_axis=MODEL_AXIS,
        )
        batch = _abstract_batch(cfg, shape)
        batch.pop("targets")
        lowered = step.lower(params, batch)
        jx = jax.make_jaxpr(step)(params, batch)
        tokens = shape.global_batch * shape.seq_len
        return lowered, jx, {"training": False, "tokens": tokens}

    # decode: one new token against a pre-filled cache of seq_len slots
    step, _ = serve_mod.build_decode_step(
        cfg, mesh, scfg, global_batch=shape.global_batch,
        target_len=shape.seq_len,
        node_axes=node_axes, model_axis=MODEL_AXIS,
    )
    cache = serve_mod.abstract_cache(
        cfg, shape.global_batch, shape.seq_len, mesh, scfg,
        node_axes=node_axes, model_axis=MODEL_AXIS,
    )
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    t = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = step.lower(params, tokens, cache, t)
    jx = jax.make_jaxpr(step)(params, tokens, cache, t)
    return lowered, jx, {"training": False, "tokens": shape.global_batch}


def run_cell(arch: str, shape_name: str, mesh_name: str, args) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    chips = mesh.size
    t0 = time.time()
    lowered, jx, meta = build_cell(cfg, shape, mesh, args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    print(f"  memory_analysis: {ma}")
    ca = compiled.cost_analysis()
    print(
        "  cost_analysis (XLA, loop bodies once): flops=%.4g bytes=%.4g"
        % (ca.get("flops", 0.0), ca.get("bytes accessed", 0.0))
    )
    coll = parse_collective_bytes(compiled.as_text())

    # trip-count-aware accounting from the jaxpr (launch/costmodel.py): XLA's
    # cost analysis counts scan bodies once, so FLOPs/collectives inside the
    # layer/microbatch/chunk scans must be multiplied out explicitly.
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    costs = analyze_jaxpr(jx.jaxpr, axis_sizes)
    print(
        "  jaxpr costs: flops=%.4g coll_bytes=%.4g (xla-text coll=%.4g)"
        % (costs.flops, costs.collective_bytes, coll.egress_bytes)
    )

    n_params = T.count_params(cfg, mesh.shape[MODEL_AXIS])
    n_active = cfg.active_param_count()
    mf = model_flops(n_active, meta["tokens"], training=meta["training"])
    flops_dev = costs.flops
    xla_bytes = float(ca.get("bytes accessed", 0.0))
    amp = (
        costs.naive_bytes / costs.naive_bytes_untripped
        if costs.naive_bytes_untripped > 0
        else 1.0
    )
    # memory term: trip-aware materialized bytes (elementwise assumed fused);
    # never below XLA's (body-once) fused figure.
    bytes_dev = max(costs.materialized_bytes, xla_bytes)
    terms = roofline_terms(
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        collective_egress=costs.collective_bytes,
    )
    util = mf / (flops_dev * chips) if flops_dev > 0 else 0.0

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok",
        "chips": chips,
        "seconds": {"lower": round(t_lower, 2), "compile": round(t_compile, 2)},
        "params": n_params,
        "active_params": n_active,
        "model_flops": mf,
        "hlo_flops_per_device": flops_dev,
        "hlo_bytes_per_device": bytes_dev,
        "xla_raw": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": xla_bytes,
            "collective_egress_text": coll.egress_bytes,
            "loop_bytes_amplification": amp,
            "naive_bytes_tripped": costs.naive_bytes,
            "materialized_bytes": costs.materialized_bytes,
        },
        "collectives": {
            "counts": costs.collective_counts,
            "egress_bytes": costs.collective_bytes,
            "breakdown_top": dict(
                sorted(
                    costs.collective_breakdown.items(),
                    key=lambda kv: -kv[1],
                )[:12]
            ),
        },
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "roofline": terms,
        "model_flops_utilization": util,
        "knobs": {
            "algorithm": args.algorithm,
            "topology": args.topology,
            "gossip_impl": args.gossip_impl,
            "compression": args.compression,
            "grad_accum": args.grad_accum,
            "remat": args.remat,
            "remat_policy": args.remat_policy,
            "decode_grouped_gqa": args.decode_grouped_gqa,
            "mlstm_chunk": args.mlstm_chunk,
            "ssm_chunk": args.ssm_chunk,
            "fused_update": args.fused_update,
        },
    }
    return rec


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all")
    p.add_argument("--shape", default="all")
    p.add_argument("--mesh", default="both", choices=["pod1", "pod2", "both"])
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--tag", default="baseline")
    p.add_argument("--algorithm", default="decentlam")
    p.add_argument("--topology", default="exp")
    p.add_argument("--gossip-impl", dest="gossip_impl", default="ppermute")
    p.add_argument("--compression", default=None)
    p.add_argument("--grad-accum", dest="grad_accum", type=int, default=0,
                   help="0 = auto (cap ~16k microbatch tokens per node)")
    p.add_argument("--remat", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--remat-policy", dest="remat_policy", default="full",
                   choices=["full", "save_collectives"])
    p.add_argument("--mlstm-chunk", dest="mlstm_chunk", type=int, default=128)
    p.add_argument("--decode-grouped-gqa", dest="decode_grouped_gqa",
                   action="store_true")
    p.add_argument("--ssm-chunk", dest="ssm_chunk", type=int, default=128)
    p.add_argument("--fused-update", dest="fused_update", action="store_true")
    p.add_argument("--gossip-serialize", dest="gossip_serialize",
                   action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]

    failures = []
    for mesh_name in meshes:
        outdir = os.path.join(args.out, args.tag, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            for shape_name in shapes:
                path = os.path.join(outdir, f"{arch}__{shape_name}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip-existing] {mesh_name} {arch} {shape_name}")
                    continue
                print(f"[dryrun] mesh={mesh_name} arch={arch} shape={shape_name}")
                try:
                    rec = run_cell(arch, shape_name, mesh_name, args)
                except Exception as e:  # noqa: BLE001 — report and continue
                    traceback.print_exc()
                    rec = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_name,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                    }
                    failures.append((mesh_name, arch, shape_name))
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(
                        "  -> compute %.3es memory %.3es collective %.3es"
                        " dominant=%s  compile %.1fs"
                        % (
                            r["compute_s"], r["memory_s"], r["collective_s"],
                            r["dominant"], rec["seconds"]["compile"],
                        )
                    )
                elif rec["status"] == "skipped":
                    print(f"  -> skipped: {rec['reason']}")

    if failures:
        print(f"\nFAILED cells: {failures}")
        raise SystemExit(1)
    print("\nAll requested cells passed.")


if __name__ == "__main__":
    main()
