"""End-to-end decentralized training driver.

Runs real training (synthetic LM data) with any algorithm x topology on
whatever devices exist — simulated CPU devices for local runs, the
production pod for real deployments.  Wires together the full stack:
data pipeline -> shard_map train step (ppermute gossip) -> checkpointing
(periodic + final) -> optional fail-stop drill (elastic shrink + resume).

Examples::

    # 8 simulated nodes on CPU, ~10M-param LM, 200 steps
    PYTHONPATH=src python -m repro.launch.train --simulate-nodes 8 \
        --preset tiny --steps 200 --algorithm decentlam --topology exp

    # reduced assigned arch
    PYTHONPATH=src python -m repro.launch.train --simulate-nodes 4 \
        --arch qwen3-0.6b --smoke --steps 50

    # ~100M model (paper-scale demo; slow on CPU, sized for real chips)
    PYTHONPATH=src python -m repro.launch.train --simulate-nodes 8 \
        --preset 100m --steps 300

``run(argv)`` is this whole command as a function: ``main()`` and
``chip_smoke.py`` call it with an argument list and read its
:class:`RunResult`.
"""

import argparse
import dataclasses
import os
import time
from typing import Any


@dataclasses.dataclass
class RunResult:
    """What one ``run`` leaves behind, for callers that check it."""

    state: Any  # final TrainState, on the mesh
    losses: list  # fleet-mean loss of every step, in order
    compile_s: float  # lower + compile of the step program
    step_s: float  # steady seconds/step after the first (block_until_ready)
    compiled: Any  # jax.stages.Compiled of the step
    mesh: Any


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--simulate-nodes", type=int, default=0,
                   help="simulate N devices on CPU (set before jax init)")
    p.add_argument("--tp", type=int, default=1, help="model-parallel size")
    p.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    p.add_argument("--arch", default=None, help="use an assigned arch instead")
    p.add_argument("--smoke", action="store_true",
                   help="with --arch: use the reduced smoke config")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--algorithm", default="decentlam")
    p.add_argument("--topology", default="exp")
    p.add_argument("--gossip-impl", dest="gossip_impl", default="ppermute")
    p.add_argument("--gossip-delay", dest="gossip_delay", type=int, default=0,
                   help="hold gossip payloads back k steps on-device "
                   "(delayed ppermute channel; SSP staleness on a real mesh)")
    p.add_argument("--compression", default=None)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--sa-damping", dest="sa_damping", type=float, default=0.5,
                   help="decentlam-sa: base of the per-gap momentum damping "
                   "(gamma = sa_damping**version_gap, read off the delayed "
                   "gossip channel)")
    p.add_argument("--sa-floor", dest="sa_floor", type=float, default=0.0,
                   help="decentlam-sa: lower bound on the damping factor")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--seq-len", dest="seq_len", type=int, default=128)
    p.add_argument("--per-node-batch", dest="per_node_batch", type=int, default=8)
    p.add_argument("--heterogeneity", type=float, default=0.2)
    p.add_argument("--grad-accum", dest="grad_accum", type=int, default=1)
    p.add_argument("--fused-impl", dest="fused_impl", default="ref",
                   choices=["ref", "pallas", "pallas_interpret"],
                   help="stage executor of the flat-plane update: the jnp "
                   "reference or the Pallas fused-update kernel")
    p.add_argument("--measure-json", dest="measure_json", default=None,
                   help="write {'measured_step_s': ...} after the run — the "
                   "calibration input of sim.wallclock.calibrate_from_dryrun")
    p.add_argument("--ckpt-dir", dest="ckpt_dir", default=None)
    p.add_argument("--ckpt-every", dest="ckpt_every", type=int, default=100)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--failure-drill", dest="failure_drill", action="store_true",
                   help="halfway: checkpoint, elastic-shrink to n/2, resume")
    p.add_argument("--serve-while-training", dest="serve_while_training",
                   action="store_true",
                   help="cooperative serving demo (README §'Serving while "
                   "training'): publish node 0's weights through the "
                   "consensus-gated WeightPublisher every --publish-every "
                   "steps and advance a continuous-batching ServeEngine one "
                   "tick per train step over a synthetic request load; "
                   "requires --tp 1")
    p.add_argument("--publish-every", dest="publish_every", type=int,
                   default=20, help="steps between publication offers")
    p.add_argument("--publish-gap-threshold", dest="publish_gap_threshold",
                   type=int, default=1,
                   help="max incident gossip version gap a node may carry "
                   "and still publish (see fleet_node_gaps)")
    p.add_argument("--serve-requests", dest="serve_requests", type=int,
                   default=8, help="synthetic requests for the serve demo")
    p.add_argument("--no-finite-guard", dest="finite_guard",
                   action="store_false",
                   help="disable the non-finite-gradient skip guard")
    p.add_argument("--max-skipped-steps", dest="max_skipped_steps", type=int,
                   default=0,
                   help="abort once this many steps had their update "
                   "skipped by the finite guard (0 = no budget)")
    p.add_argument("--chaos", action="append", default=None, metavar="SPEC",
                   help="inject a wire fault (repeatable).  SPEC is "
                   "'KIND[,key=val...]' with KIND in silence|drop|dup|"
                   "delay|corrupt|nan and keys nodes=0-2 (range) or "
                   "nodes=0.3.5 (list), start=, stop=, prob=, frac=, bit=. "
                   "e.g. --chaos 'drop,prob=0.2' "
                   "--chaos 'silence,nodes=0-1,start=50,stop=120'")
    p.add_argument("--chaos-seed", dest="chaos_seed", type=int, default=0)
    p.add_argument("--resilient", action="store_true",
                   help="wrap the transport in the self-healing "
                   "ResilientChannel (trust-masked mixing with W-row "
                   "renormalization + NaN/Inf payload quarantine) and "
                   "drive its trust mask from a gap-based HealthMonitor")
    p.add_argument("--resilient-gap", dest="resilient_gap", type=int,
                   default=None,
                   help="on-device auto-distrust bound on a sender's "
                   "version gap (None = host monitor only)")
    p.add_argument("--health-every", dest="health_every", type=int, default=1,
                   help="steps between host health-monitor observations "
                   "when --resilient is set")
    p.add_argument("--log-every", dest="log_every", type=int, default=10)
    p.add_argument("--track-consensus", dest="track_consensus",
                   action="store_true")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--profile-dir", dest="profile_dir", default=None,
                   help="write a jax.profiler trace of --profile-steps here "
                        "(TensorBoard, Perfetto)")
    p.add_argument("--profile-steps", dest="profile_steps", type=_step_range,
                   default=(1, 4), metavar="A:B",
                   help="steps A to B-1 go into the --profile-dir trace")
    return p.parse_args(argv)


def _step_range(text: str) -> tuple[int, int]:
    """``A:B`` -> (A, B): the steps A to B - 1."""
    try:
        a, b = (int(x) for x in text.split(":"))
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected A:B, got {text!r}") from None
    if not 0 <= a < b:
        raise argparse.ArgumentTypeError(f"expected 0 <= A < B, got {text!r}")
    return a, b


class StepProfiler:
    """A ``jax.profiler`` trace of steps ``first`` to ``end - 1`` into
    ``out_dir``, each step inside ``StepTraceAnnotation("train",
    step_num=k)``.  The program's own spans land in the same trace: the
    input pipeline's ``repro.input.*`` on the host, and the named scopes
    (``model``, ``update_tail``, ...) in the device ops' metadata.  Without
    ``out_dir`` it does nothing."""

    def __init__(self, out_dir: str | None, steps: tuple[int, int]):
        self.out_dir = out_dir
        self.first, self.end_at = steps
        self._span = None
        self._on = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    def begin(self, step: int) -> None:
        """Before step ``step`` is dispatched."""
        if self.out_dir is None or not self.first <= step < self.end_at:
            return
        import jax

        if not self._on:
            jax.profiler.start_trace(self.out_dir)
            self._on = True
        self._span = jax.profiler.StepTraceAnnotation("train", step_num=step)
        self._span.__enter__()

    def end(self, step: int, state) -> None:
        """After step ``step``'s host work; the trace stops after the last
        traced step's device work."""
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self._on and step + 1 >= self.end_at:
            self.stop(state)

    def stop(self, state=None) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self._on:
            import jax

            if state is not None:
                jax.block_until_ready(state)
            jax.profiler.stop_trace()
            self._on = False


def _parse_chaos(specs, seed):
    """Build a ChaosSchedule from repeated --chaos 'KIND[,key=val...]' specs."""
    from ..resilience import (
        BitCorrupt, ChaosSchedule, Drop, Duplicate, ExtraDelay, NaNInject,
        PeerSilence,
    )

    kinds = {"silence": PeerSilence, "drop": Drop, "dup": Duplicate,
             "delay": ExtraDelay, "corrupt": BitCorrupt, "nan": NaNInject}
    faults = []
    for spec in specs:
        kind, _, rest = spec.partition(",")
        if kind not in kinds:
            raise SystemExit(
                f"--chaos: unknown kind {kind!r} (want {'|'.join(kinds)})"
            )
        kw = {}
        for item in filter(None, rest.split(",")):
            k, _, v = item.partition("=")
            if k == "nodes":
                if "-" in v:
                    lo, hi = v.split("-")
                    kw["nodes"] = tuple(range(int(lo), int(hi) + 1))
                else:
                    kw["nodes"] = tuple(int(i) for i in v.split("."))
            elif k in ("start", "stop", "bit"):
                kw[k] = int(v)
            elif k in ("prob", "frac"):
                kw[k] = float(v)
            else:
                raise SystemExit(f"--chaos: unknown key {k!r} in {spec!r}")
        try:
            faults.append(kinds[kind](**kw))
        except TypeError as e:
            raise SystemExit(f"--chaos: {spec!r}: {e}")
    return ChaosSchedule(faults=tuple(faults), seed=seed)


def model_config(args):
    """The ModelConfig the parsed command line trains."""
    from ..configs import get_config, tiny_lm

    if args.arch:
        return get_config(args.arch, smoke=args.smoke)
    if args.preset == "100m":
        return tiny_lm("lm-100m", n_layers=12, d_model=768, n_heads=12,
                       n_kv_heads=4, d_ff=3072, vocab_size=50304)
    return tiny_lm()


def train_config(args):
    """The TrainConfig the parsed command line trains with."""
    from ..core.schedules import ScheduleConfig
    from ..models.transformer import RuntimeConfig
    from ..train.step import TrainConfig

    return TrainConfig(
        algorithm=args.algorithm,
        topology=args.topology,
        gossip_impl=args.gossip_impl,
        gossip_delay=args.gossip_delay,
        compression=args.compression,
        momentum=args.momentum,
        sa_damping=args.sa_damping,
        sa_floor=args.sa_floor,
        grad_accum=args.grad_accum,
        schedule=ScheduleConfig(
            kind="warmup_cosine", peak_lr=args.lr,
            warmup_steps=min(args.warmup, max(args.steps // 5, 1)),
            total_steps=max(args.steps, 2),
        ),
        runtime=RuntimeConfig(dtype=args.dtype, remat=False),
        fused_impl=args.fused_impl,
        track_consensus=args.track_consensus,
        finite_guard=args.finite_guard,
        chaos=_parse_chaos(args.chaos, args.chaos_seed) if args.chaos else None,
        resilient=args.resilient,
        resilient_gap=args.resilient_gap,
    )


def main() -> None:
    run()


def run(argv=None) -> RunResult:
    """Train as the command line ``argv`` says (``sys.argv`` when None)."""
    args = parse_args(argv)
    if args.simulate_nodes:
        total = args.simulate_nodes * args.tp
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={total}"
        )

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .compile_cache import configure_compile_cache
    from .mesh import make_mesh

    configure_compile_cache()

    from ..core.optimizers import make_optimizer
    from ..data.pipeline import prefetch_to_device
    from ..data.synthetic import SyntheticLM, SyntheticLMConfig
    from ..train.checkpoint import (
        check_plane_manifest,
        elastic_reshape,
        restore_checkpoint,
        save_checkpoint,
    )
    from ..train.step import build_train_step
    from ..train.train_state import (
        ensure_channel_state,
        init_train_state,
        model_plane_layout,
        reconcile_plane_state,
    )

    n_devices = len(jax.devices())
    tp = args.tp
    n_nodes = n_devices // tp
    assert n_nodes * tp == n_devices, (n_devices, tp)
    mesh = make_mesh((n_nodes, tp), ("data", "model"))
    print(f"mesh: {n_nodes} nodes x {tp}-way TP ({n_devices} devices)")

    cfg = model_config(args)
    tcfg = train_config(args)

    def build(mesh, n_nodes):
        step_fn, sspecs, bspecs, channel = build_train_step(
            cfg, tcfg, mesh, node_axes=("data",)
        )
        opt = make_optimizer(tcfg.opt_config())
        bshard = jax.tree.map(
            lambda s: NamedSharding(mesh, s), bspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return step_fn, opt, channel, bshard

    step_fn, opt, channel, bshard = build(mesh, n_nodes)
    layout = model_plane_layout(cfg, tp)

    if args.resume and args.ckpt_dir:
        host_state, manifest = restore_checkpoint(args.ckpt_dir)
        if jax.tree.leaves(host_state["params"])[0].shape[0] != n_nodes:
            print(f"elastic reshape {manifest.get('n_nodes')} -> {n_nodes}")
            host_state = elastic_reshape(host_state, n_nodes)
        # checkpoints resume across tensor-parallel degrees: a plane-form
        # opt state written at a different tp (the manifest's "plane_tp")
        # round-trips through the stored layout's global tree before
        # repacking for this mesh.  Manifests without "plane_tp" were
        # written at tp == 1; tree-form opt states in them are packed.
        stored_tp = int(manifest.get("plane_tp") or 1)
        stored_layout = (
            model_plane_layout(cfg, stored_tp) if stored_tp != tp else layout
        )
        check_plane_manifest(manifest, stored_layout)
        host_state = reconcile_plane_state(host_state, layout, stored_layout)
        # channel state (delay buffers, error feedback, telemetry) resumes
        # when shapes match; anything missing/invalidated re-inits to zeros
        state = ensure_channel_state(host_state, channel, n_nodes, layout)
        start = int(state["step"])
        print(f"resumed from step {start}")
    else:
        state = init_train_state(
            jax.random.key(0), cfg, opt, n_nodes, tp, mesh=mesh,
            node_axes=("data",), channel=channel, plane_layout=layout,
        )
        start = 0

    data = SyntheticLM(SyntheticLMConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        per_node_batch=args.per_node_batch, n_nodes=n_nodes,
        heterogeneity=args.heterogeneity,
    ))

    def batch_fn(k):
        # host numpy until the sharded device_put in the prefetch thread
        return data.batch(start + k)

    serve = None
    if args.serve_while_training:
        import numpy as np

        from ..core.gossip import fleet_node_gaps
        from ..serve import Request, ServeEngine, WeightPublisher

        assert tp == 1, "--serve-while-training requires --tp 1"
        pub = WeightPublisher(
            layout, gap_threshold=args.publish_gap_threshold,
        )
        engine = ServeEngine(
            cfg, mesh, slots=4, max_prompt=32, max_new=16,
            runtime=tcfg.runtime, publisher=pub,
        )
        srng = np.random.default_rng(7)
        for i in range(args.serve_requests):
            n = int(srng.integers(4, 33))
            engine.submit(Request(
                rid=i,
                tokens=srng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=16,
            ))

        def serve(step, state):
            """One cooperative slice: maybe publish, then one engine tick."""
            if step % args.publish_every == 0:
                gaps = fleet_node_gaps(channel, state["channel"])
                # node 0 publishes its own iterate (params stay tree-form in
                # the TrainState; only opt/channel hot state is
                # plane-packed)
                src = jax.tree.map(lambda x: np.asarray(x)[0], state["params"])
                shipped = pub.offer(src, version=step + 1, gap=int(gaps[0]))
                print(f"publish v{step + 1} gap={int(gaps[0])} -> "
                      f"{'shipped' if shipped else 'held (gate)'}", flush=True)
            engine.tick()

    monitor = None
    if args.resilient:
        import numpy as np

        from ..resilience import HealthMonitor, fleet_sender_gaps, with_trust

        monitor = HealthMonitor(n_nodes)
        applied_trust = monitor.trust.copy()
    skipped_steps = 0

    t0 = time.time()
    t_warm = None  # set after step 0 so the steady window excludes compile
    compiled, compile_s = None, 0.0
    losses = []
    profiler = StepProfiler(args.profile_dir, args.profile_steps)
    it = prefetch_to_device(batch_fn, bshard, args.steps - start)
    with profiler:
        for k, batch in enumerate(it):
            step = start + k
            if k == 0:
                # compile ahead of the first call (the jit reuses this
                # executable), so compile time is reported on its own
                t_c = time.perf_counter()
                compiled = step_fn.lower(state, batch).compile()
                compile_s = time.perf_counter() - t_c
            profiler.begin(step)
            state, metrics = step_fn(state, batch)
            losses.append(metrics["loss"])
            if k == 0:
                jax.block_until_ready(metrics["loss"])
                t_warm = time.perf_counter()
            if args.max_skipped_steps and float(metrics["skipped_nonfinite"]) > 0:
                skipped_steps += 1
                if skipped_steps > args.max_skipped_steps:
                    raise RuntimeError(
                        f"aborting at step {step}: the finite guard skipped the "
                        f"optimizer update on {skipped_steps} steps, exceeding "
                        f"--max-skipped-steps={args.max_skipped_steps} — the "
                        "gradients are persistently non-finite (check lr/data/"
                        "fault injection)"
                    )
            if monitor is not None and step % args.health_every == 0:
                trust = monitor.observe(
                    fleet_sender_gaps(channel, state["channel"])
                )
                if not np.array_equal(trust, applied_trust):
                    state = dict(state)
                    state["channel"] = with_trust(state["channel"], trust)
                    applied_trust = trust.copy()
                    print(f"health: {monitor.states()} (step {step})", flush=True)
            if serve is not None:
                serve(step, state)
            if step % args.log_every == 0 or step == args.steps - 1:
                msg = (f"step {step:5d} loss {float(metrics['loss']):.4f} "
                       f"lr {float(metrics['lr']):.2e}")
                if args.track_consensus:
                    msg += f" consensus {float(metrics['consensus_sq']):.3e}"
                print(msg, flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                path = save_checkpoint(args.ckpt_dir, jax.device_get(state),
                                       metadata={"n_nodes": n_nodes,
                                                 "algorithm": args.algorithm},
                                       plane_layout=layout)
                print(f"checkpointed -> {path}")
            profiler.end(step, state)
            if args.failure_drill and step == (start + args.steps) // 2:
                print("FAILURE DRILL: checkpoint, shrink to n/2, rebuild, resume")
                host = jax.device_get(state)
                new_n = max(1, n_nodes // 2)
                host = elastic_reshape(host, new_n)
                mesh2 = make_mesh((new_n, tp), ("data", "model"),
                                  devices=jax.devices()[: new_n * tp])
                step_fn, opt, channel, bshard = build(mesh2, new_n)
                host = ensure_channel_state(host, channel, new_n, layout)
                state = jax.tree.map(jnp.asarray, host)
                data = SyntheticLM(SyntheticLMConfig(
                    vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    per_node_batch=args.per_node_batch, n_nodes=new_n,
                    heterogeneity=args.heterogeneity,
                ))
                n_nodes = new_n
                remaining = args.steps - step - 1
                it2 = prefetch_to_device(
                    lambda k2: data.batch(step + 1 + k2), bshard, remaining,
                )
                for k2, batch2 in enumerate(it2):
                    state, metrics = step_fn(state, batch2)
                    losses.append(metrics["loss"])
                    s2 = step + 1 + k2
                    if s2 % args.log_every == 0 or s2 == args.steps - 1:
                        print(f"step {s2:5d} loss {float(metrics['loss']):.4f} "
                              f"(post-failure, {new_n} nodes)", flush=True)
                break

    jax.block_until_ready(state)
    dt = time.time() - t0
    n_steps = args.steps - start
    step_s = (
        (time.perf_counter() - t_warm) / (n_steps - 1)
        if t_warm is not None and n_steps > 1 else float("nan")
    )
    print(f"done: {n_steps} steps in {dt:.1f}s (compile {compile_s:.2f}s, "
          f"steady {step_s:.4f}s/step)")
    if serve is not None:
        # drain whatever the cooperative ticks left in flight (unless the
        # gate never cleared a single version — nothing to serve with)
        done = engine.run_until_drained() if pub.current else engine.completions
        ps, es = pub.stats(), engine.stats()
        print(f"serve: {len(done)}/{args.serve_requests} requests done, "
              f"{es['swaps']} weight swap(s); published "
              f"{ps['published']}/{ps['offers']} offers "
              f"(rate {ps['publish_rate']:.2f}, threshold "
              f"{ps['gap_threshold']}, final v{ps['current_version']})")
    if args.measure_json:
        import json
        if n_steps > 1:
            # steady-state price: exclude step 0 (XLA compile dominates it)
            measured, warm_steps = step_s, n_steps - 1
        else:
            measured, warm_steps = dt / max(1, n_steps), n_steps
        with open(args.measure_json, "w") as f:
            json.dump({
                "measured_step_s": measured,
                "steps": warm_steps,
                "n_nodes": n_nodes,
                "algorithm": args.algorithm,
                "arch": args.arch or args.preset,
            }, f, indent=2)
        print(f"wrote {args.measure_json} (measured_step_s={measured:.4g})")
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, jax.device_get(state),
                        metadata={"n_nodes": n_nodes,
                                  "algorithm": args.algorithm},
                        plane_layout=layout)
    return RunResult(
        state=state, losses=[float(x) for x in losses], compile_s=compile_s,
        step_s=step_s, compiled=compiled, mesh=mesh,
    )


if __name__ == "__main__":
    main()
