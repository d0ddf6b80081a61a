"""One run of one cell: set-up, the measured window, the trace, the check.

The window drives the program's normal training path, wired as
``repro.launch.train.run`` wires it: the model from ``configs.get_config``
cut as the configuration file says, ``train.step.build_train_step`` on a
``(nodes, 1)`` mesh from ``launch.mesh.make_mesh``, the state from
``train.train_state.make_train_state_fn`` (with the benchmark's weights
in place of the program's), and batches through
``data.pipeline.prefetch_to_device``.

Set-up compiles the step once and drives the compiled step through its
first ``check_steps`` steps, on the window's own feed; those steps are the
warm-up and the steps the reference follows.  The window then dispatches
step k and blocks on step k-1's loss, so one step stays in flight.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import math
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable

import check
import flops
import reference
import trace_reduce
import weights
from traffic import TrafficLM

TRACE_STEPS = 8
COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/cache_")


class _Stopped(Exception):
    pass


class Feed:
    """The program's prefetch iterator over the traffic, closable."""

    def __init__(self, batch_fn: Callable[[int], Any], shardings):
        from repro.data.pipeline import prefetch_to_device

        self._stop = threading.Event()

        def fn(k):
            if self._stop.is_set():
                raise _Stopped()
            return batch_fn(k)

        self._it = prefetch_to_device(fn, shardings, 2**62)

    def next(self):
        return next(self._it)

    def close(self):
        """Stop the producer, drop what it queued and join its thread."""
        self._stop.set()
        try:
            for _ in self._it:
                pass
        except _Stopped:
            pass


class CompileCounter:
    """Counts compilations (and persistent-cache loads) JAX reports."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0

        def on(key, *_a, **_k):
            if key.startswith(COMPILE_EVENTS):
                self.n += 1

        mon.register_event_duration_secs_listener(on)
        mon.register_event_listener(on)


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file, checked
    against it key by key."""
    from repro.configs import get_config

    p = cfg["program"]
    mc = dataclasses.replace(
        get_config(p["arch"], smoke=bool(p.get("smoke"))),
        n_layers=int(cfg["num_hidden_layers"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        router_aux_weight=float(cfg.get("router_aux_loss_coef", 0.0)),
        capacity_factor=float(cfg.get("capacity_factor", 1.25)),
    )
    m = weights.dims(cfg)
    want = dict(d_model=m["d"], n_heads=m["h"], n_kv_heads=m["kv"], hd=m["hd"],
                d_ff=m["f"], vocab_size=m["v"], n_experts=m["e"], top_k=m["k"],
                rope_theta=float(cfg["rope_theta"]), qk_norm=bool(cfg["qk_norm"]),
                act=cfg["hidden_act"], norm_type="rmsnorm", gated_mlp=True,
                sliding_window=0, logit_softcap=0.0)
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"the program's {p['arch']} is not the configuration "
                         f"file: {got} != {want}")
    for key, identity in (("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
                          ("logits_scaling", 1.0),
                          ("attention_multiplier", 1.0 / math.sqrt(m["hd"]))):
        if float(cfg.get(key, identity)) != identity:
            raise ValueError(f"the program has no {key}; the file states {cfg[key]}")
    if float(cfg.get("rms_norm_eps", 1e-6)) != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    if float(cfg.get("router_z_loss_coef", 1e-3 if m["e"] else 0.0)) != (
            1e-3 if m["e"] else 0.0):
        raise ValueError("the program's router z-loss weight is 1e-3")
    return mc


def train_config(cfg: dict, traffic: dict):
    from repro.core.schedules import ScheduleConfig
    from repro.models.transformer import RuntimeConfig
    from repro.train.step import TrainConfig

    p = cfg["program"]
    return TrainConfig(
        algorithm=traffic["algorithm"],
        topology=traffic["topology"],
        gossip_impl=traffic["gossip_impl"],
        momentum=float(traffic["momentum"]),
        schedule=ScheduleConfig(kind="constant", peak_lr=float(traffic["lr"])),
        runtime=RuntimeConfig(dtype=p["compute_dtype"], remat=bool(p["remat"]),
                              attn_impl=p["attn_impl"]),
        fused_update=True,
        fused_impl=traffic["fused_impl"],
        flat_planes=bool(traffic["flat_planes"]),
    )


class Program:
    """The system under test, built once for a cell; states come from seeds."""

    def __init__(self, cell, devices, *, wrap_step=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.optimizers import make_optimizer
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as T
        from repro.train.step import build_train_step
        from repro.train.train_state import make_train_state_fn, model_plane_layout

        self.cell = cell
        self.cfg, self.traffic = cell.config, cell.traffic
        if self.cfg["program"]["param_dtype"] != "float32":
            raise ValueError("the program keeps float32 parameters")
        self.n = int(self.traffic["nodes"])
        self.devices = list(devices)[: self.n]
        self.mc = model_config(self.cfg)
        self.tcfg = train_config(self.cfg, self.traffic)
        self.mesh = make_mesh((self.n, 1), ("data", "model"), devices=self.devices)
        step_fn, sspecs, bspecs, channel = build_train_step(
            self.mc, self.tcfg, self.mesh, node_axes=("data",))
        self.step_fn = step_fn
        self.wrap_step = wrap_step
        self.layout = model_plane_layout(self.mc, 1)

        program_shapes = jax.tree.map(
            lambda a: tuple(a.shape),
            jax.eval_shape(lambda k: T.init_params(k, self.mc, 1), jax.random.key(0)))
        if program_shapes != weights.param_shapes(self.cfg):
            raise ValueError("the program's parameter tree is not the one the "
                             "benchmark draws weights for")

        def shard(spec):
            return NamedSharding(self.mesh, spec)

        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        self.state_sharding = jax.tree.map(shard, sspecs, is_leaf=is_spec)
        self.batch_sharding = jax.tree.map(shard, bspecs, is_leaf=is_spec)
        init_fn = make_train_state_fn(
            self.mc, make_optimizer(self.tcfg.opt_config()), self.n, 1, channel,
            self.layout if self.tcfg.flat_planes else None)
        n, cfg = self.n, self.cfg

        def init(key):
            st = init_fn(key)
            st["params"] = jax.tree.map(
                lambda a: jax.numpy.broadcast_to(a[None], (n,) + a.shape),
                weights.build_params(key, cfg))
            return st

        self._init = jax.jit(init, out_shardings=self.state_sharding)
        layout, flat = self.layout, self.tcfg.flat_planes
        self._grad_norms = jax.jit(lambda m: check.leaf_norms(
            layout.unpack(m, dtype=jax.numpy.float32, leading=1) if flat else m))
        self._change_norms = jax.jit(lambda x, key: check.leaf_norms(jax.tree.map(
            lambda a, b: a - b[None], x, weights.build_params(key, cfg))))
        self.compiled = None
        self.step = None

    def start(self, seed: int):
        """State and feed of ``seed``; compiles the step on first use."""
        self.seed = int(seed)
        self.data = TrafficLM(self.traffic, self.mc.vocab_size, self.seed)
        state = self._init(weights.seed_key(self.seed))
        feed = Feed(self.data.batch, self.batch_sharding)
        return state, feed

    def first_steps(self, state, feed):
        """The set-up's steps through the compiled step: the program's side
        of the check.  Returns (state, readings)."""
        import jax

        got = {"loss": []}
        for k in range(int(self.traffic["check_steps"])):
            batch = feed.next()
            if self.compiled is None:
                self.compiled = self.step_fn.lower(state, batch).compile()
                self.step = (self.wrap_step(self.compiled) if self.wrap_step
                             else self.compiled)
            state, met = self.step(state, batch)
            got["loss"].append(float(met["loss"]))
            if k == 0:
                got["grad"] = check.flat_norms(self._grad_norms(state["opt"]["m"]))
        got["change"] = check.flat_norms(
            self._change_norms(state["params"], weights.seed_key(self.seed)))
        jax.block_until_ready(state)
        return state, got

    def check_batches(self):
        return [self.data.batch(k) for k in range(int(self.traffic["check_steps"]))]

    def hbm_gib(self) -> float:
        ma = self.compiled.memory_analysis()
        return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30

    def kernel_names(self) -> list[str]:
        """Instruction names of the update kernel in the compiled step: the
        ``tpu_custom_call``s whose Mosaic body comes from the fused-update
        kernel module."""
        import base64
        import re

        names = []
        for line in self.compiled.as_text().splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            body = re.search(r'"body":"([^"]*)"', line)
            raw = base64.b64decode(body.group(1)) if body else b""
            if b"fused_update" in raw or b"fused_stage_kernel" in raw:
                names.append(line.split("=", 1)[0].strip().lstrip("%"))
        return names


def free(tree) -> None:
    import jax

    for a in jax.tree.leaves(tree):
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()
    gc.collect()


def window(program: Program, state, feed, seconds: float, t_start: float,
           counter: CompileCounter):
    """The measured window.  Returns (state, record of the window)."""
    import jax

    step = program.step
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    n_compiles = counter.n
    done, waits, bad = [], [], 0
    prev = None
    while True:
        if prev is not None and time.perf_counter() - t0 >= seconds:
            break
        tw = time.perf_counter()
        batch = feed.next()
        waits.append(time.perf_counter() - tw)
        state, met = step(state, batch)
        if prev is not None:
            loss, skipped = jax.device_get((prev["loss"], prev["skipped_nonfinite"]))
            done.append(time.perf_counter())
            bad += (not math.isfinite(float(loss))) or float(skipped) > 0
        prev = met
    loss, skipped = jax.device_get((prev["loss"], prev["skipped_nonfinite"]))
    done.append(time.perf_counter())
    bad += (not math.isfinite(float(loss))) or float(skipped) > 0
    return state, {
        "t0": t0, "setup_s": setup_s, "completions": done, "input_wait_s": waits,
        "failed": bad, "compiles": counter.n - n_compiles,
    }


def traced(program: Program, state, feed, counter: CompileCounter):
    """TRACE_STEPS steps under the profiler, host phases annotated."""
    import jax
    from jax.profiler import TraceAnnotation

    step = program.step
    out = tempfile.mkdtemp(prefix="bench_trace_")
    n_compiles = counter.n
    waits, bad, prev = [], 0, None
    jax.profiler.start_trace(out)
    try:
        for _ in range(TRACE_STEPS):
            with TraceAnnotation("bench.input_wait"):
                tw = time.perf_counter()
                batch = feed.next()
                waits.append(time.perf_counter() - tw)
            with TraceAnnotation("bench.dispatch"):
                state, met = step(state, batch)
            if prev is not None:
                with TraceAnnotation("bench.device_wait"):
                    loss = float(jax.device_get(prev["loss"]))
                bad += not math.isfinite(loss)
            prev = met
        with TraceAnnotation("bench.device_wait"):
            loss = float(jax.device_get(prev["loss"]))
        bad += not math.isfinite(loss)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    tr = trace_reduce.load(paths[0])
    shutil.rmtree(out, ignore_errors=True)
    return state, {"trace": tr, "steps": TRACE_STEPS, "input_wait_s": waits,
                   "failed": bad, "compiles": counter.n - n_compiles}


def trace_summary(tr, lo: float, hi: float) -> dict:
    busy = trace_reduce.busy(tr, lo, hi)
    if not busy or max(busy.values()) <= 0:
        raise RuntimeError("no operation ran on the device in the traced window")
    ops: dict[str, float] = {}
    labels = {}
    for d, lst in tr.ops.items():
        labels.update((o.instr, o.label) for o in lst)
        for k, v in trace_reduce.self_times(lst, lo, hi).items():
            ops[k] = ops.get(k, 0.0) + v / len(tr.ops)
    first = min(tr.ops)
    return {
        "busy_s": trace_reduce.mean(busy),
        "window_s": hi - lo,
        "device_ops": [(labels[k], v) for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": trace_reduce.idle_gaps(tr, first, lo, hi)[:10],
    }


def run_cell(cell, seed: int, seconds: float, trace: bool, *, devices, peaks: dict,
             t_start: float, wrap_step=None) -> tuple[dict, list]:
    """Everything of one run but the printing; returns the result."""
    counter = CompileCounter()
    program = Program(cell, devices, wrap_step=wrap_step)
    state, feed = program.start(seed)
    state, got = program.first_steps(state, feed)
    rec = {
        "cell": cell.name, "config": cell.config, "traffic": cell.traffic,
        "chips": cell.chips, "peaks": peaks,
        "tokens_per_step": program.data.tokens_per_step,
        "flops_per_token": flops.flops_per_token(cell.config,
                                                 int(cell.traffic["seq_len"])),
        "update_bytes_per_node": flops.update_bytes_per_node(cell.config),
        "kernel_names": program.kernel_names(),
        "hbm_gib": program.hbm_gib(),
    }
    if trace:
        state, w = traced(program, state, feed, counter)
        lo, hi = trace_reduce.window(w["trace"])
        rec.update(w, window_lo=lo, window_hi=hi)
        summary = trace_summary(w["trace"], lo, hi)
        attempted = w["steps"]
    else:
        state, w = window(program, state, feed, seconds, t_start, counter)
        rec.update(w)
        attempted = len(w["completions"])
    if w["compiles"]:
        raise RuntimeError(f"{w['compiles']} compilations inside the window")
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in program.devices)
    feed.close()
    free(state)
    del state
    ref = reference.run(cell.config, cell.traffic, program.seed,
                        program.check_batches(), devices=program.devices)
    ok, rows = check.judge(check.numbers(got, ref), cell.limits)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader.read(rec)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    d0 = program.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(program.devices), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": attempted, "failed": int(w["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary["device_ops"]],
            "idle_gaps": [[k, v] for k, v in summary["idle_gaps"]],
        }
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    return result, rows
