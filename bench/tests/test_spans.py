"""The program's layer scopes and input spans, and their reduction
(``bench/spans.py``), on the CPU."""

import glob
import threading
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import harness
import spans
import trace_reduce as tr
from repro.data.pipeline import prefetch_to_device

DATA = Path(__file__).with_name("data")
SEED = 2**33 + 11


@pytest.fixture(scope="module")
def smoke_spans():
    """``op_name`` of every instruction of the qwen3-smoke step, compiled
    through the harness's own path."""
    import spec

    cell = spec.load_cell("qwen3-smoke.1node", benchmark=DATA / "BENCHMARK.json",
                          files=DATA)
    program = harness.Program(cell, jax.devices()[:1])
    state, feed = program.start(SEED)
    try:
        state, _ = program.first_steps(state, feed)
    finally:
        feed.close()
    return spans.op_spans(program.compiled.as_text())


def test_every_scope_is_in_the_compiled_step(smoke_spans):
    names = {spans.unwrap(seg)[1] for op in smoke_spans.values()
             for path in op.split(";") for seg in spans.segments(path)}
    assert {"model", "update_tail", *spans.SCOPES} <= names


def test_each_phase_holds_instructions(smoke_spans):
    phases = {spans.classify(op)[0] for op in smoke_spans.values()}
    assert {"fwd", "bwd", "recompute", "update_tail"} <= phases
    scoped = {s for op in smoke_spans.values() for s in spans.classify(op)[1]}
    assert scoped == set(spans.SCOPES)


@pytest.mark.parametrize("op_name,phase,scopes", [
    ("jit(step_fn)/jvp(model)/while/body/closed_call/attn_core/dot_general",
     "fwd", {"attn_core"}),
    ("jit(step_fn)/transpose(jvp(model))/while/body/closed_call/checkpoint/"
     "attn_core/mul", "bwd", {"attn_core"}),
    ("jit(step_fn)/transpose(jvp(model))/while/body/closed_call/checkpoint/"
     "rematted_computation/attn_core/tanh", "recompute", {"attn_core"}),
    ("jit(step_fn)/transpose(jvp(model))/lm_head/dot_general", "bwd", {"lm_head"}),
    ("jit(step_fn)/update_tail/plane_pack/concatenate", "update_tail",
     {"plane_pack"}),
    ("jit(step_fn)/update_tail/broadcast_in_dim;jit(step_fn)/update_tail/"
     "plane_unpack/reshape", "update_tail", {"plane_unpack"}),
    ("jit(step_fn)/squeeze;jit(step_fn)/update_tail/plane_pack/reshape",
     "update_tail", {"plane_pack"}),
    ("jit(step_fn)/transpose(jvp())/while/body/mul", "unattributed", set()),
    ("jit(step_fn)/jvp(models)/add", "unattributed", set()),
    ("reduce_sum", "unattributed", set()),
    ("", "unattributed", set()),
])
def test_classify(op_name, phase, scopes):
    assert spans.classify(op_name) == (phase, frozenset(scopes))


def test_segments_and_unwrap():
    assert spans.segments("jit(f)/transpose(jvp(a/b))/c") == [
        "jit(f)", "transpose(jvp(a/b))", "c"]
    assert spans.unwrap("transpose(jvp(model))") == (("transpose", "jvp"), "model")
    assert spans.unwrap("model") == ((), "model")


def test_op_spans_reads_the_metadata():
    text = "\n".join([
        'ENTRY %main {',
        '  %fusion.3 = f32[8]{0} fusion(f32[8] %a), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(step_fn)/jvp(model)/add" source_file="x.py"}',
        '  ROOT %copy.1 = f32[8]{0} copy(%fusion.3)',
        '  %while.2 = (s32[]) while(%t), body=%b, metadata={op_name="jit(step_fn)/'
        'transpose(jvp(model))/while"}',
        '}'])
    assert spans.op_spans(text) == {
        "fusion.3": "jit(step_fn)/jvp(model)/add",
        "while.2": "jit(step_fn)/transpose(jvp(model))/while"}


def _trace(ops):
    return tr.Trace(ops={0: ops}, async_ops={}, host=[(0.0, 100.0, "bench.dispatch")])


def test_self_time_by_phase_sums_to_busy():
    """A while and its body: the loop keeps only its own time, and the
    phases together are the busy time."""
    ops = [tr.Op(0, 10, "while.1", "while"), tr.Op(1, 4, "fusion.1", "fusion"),
           tr.Op(5, 9, "fusion.2", "fusion"), tr.Op(12, 20, "kernel.1", "custom-call"),
           tr.Op(21, 22, "copy.1", "copy")]
    names = {"while.1": "jit(s)/jvp(model)/while",
             "fusion.1": "jit(s)/jvp(model)/while/body/attn_core/dot",
             "fusion.2": "jit(s)/transpose(jvp(model))/while/body/checkpoint/"
                         "rematted_computation/attn_core/exp",
             "kernel.1": "jit(s)/update_tail/pallas_call"}
    trace = _trace(ops)
    layers = spans.layer_seconds(trace, names, 0.0, 100.0)
    assert layers["fwd"] == pytest.approx(3 + 3)  # the loop's 3 s and fusion.1
    assert layers["bwd"] == 0.0
    assert layers["recompute"] == pytest.approx(4)
    assert layers["update_tail"] == pytest.approx(8)
    assert layers["unattributed"] == pytest.approx(1)  # copy.1 has no op_name
    assert layers["attn_core"] == pytest.approx(7)
    assert layers["plane_pack"] == layers["plane_unpack"] == 0.0
    busy = tr.busy(trace, 0.0, 100.0)[0]
    assert sum(layers[k] for k in spans.PHASES) == pytest.approx(busy)


def test_layer_seconds_clip_to_the_window_and_average_the_chips():
    names = {"a": "jit(s)/jvp(model)/add", "b": "jit(s)/update_tail/mul"}
    trace = tr.Trace(ops={0: [tr.Op(0, 4, "a", "fusion"), tr.Op(6, 10, "b", "fusion")],
                          1: [tr.Op(0, 2, "a", "fusion")]},
                     async_ops={}, host=[])
    layers = spans.layer_seconds(trace, names, 1.0, 8.0)
    assert layers["fwd"] == pytest.approx((3 + 1) / 2)
    assert layers["update_tail"] == pytest.approx(2 / 2)


def test_exclusive_times_are_self_times_where_ops_nest():
    ops = [tr.Op(0, 10, "while.1", "while"), tr.Op(0, 3, "a", "fusion"),
           tr.Op(5, 9, "b", "fusion"), tr.Op(6, 7, "c", "fusion"),
           tr.Op(12, 20, "a", "fusion")]
    assert spans.exclusive_times(ops, 1, 18) == pytest.approx(
        tr.self_times(ops, 1, 18))


def test_exclusive_times_count_an_overlap_once():
    """Ops that overlap without nesting: each instant goes to the op that
    started last, so the times sum to the union."""
    ops = [tr.Op(0, 10, "a", "fusion"), tr.Op(2, 5, "b", "fusion"),
           tr.Op(4, 8, "c", "copy-done"), tr.Op(9, 12, "d", "fusion")]
    got = spans.exclusive_times(ops, 0, 11)
    assert got == pytest.approx({"a": 2 + 1, "b": 2, "c": 4, "d": 2})
    assert sum(got.values()) == pytest.approx(tr.busy(
        tr.Trace(ops={0: ops}, async_ops={}, host=[]), 0, 11)[0])


def test_a_program_without_scopes_reads_nothing():
    """The parent of the scopes: its time is unattributed but remat's
    recompute, which JAX names itself, and nothing raises."""
    ops = [tr.Op(0, 1, "fusion.1", "fusion"), tr.Op(2, 3, "fusion.2", "fusion")]
    names = {"fusion.1": "jit(s)/jvp()/while/body/add",
             "fusion.2": "jit(s)/transpose(jvp())/while/body/checkpoint/"
                         "rematted_computation/mul"}
    layers = spans.layer_seconds(_trace(ops), names, 0.0, 100.0)
    assert layers["unattributed"] == pytest.approx(1)
    assert layers["recompute"] == pytest.approx(1)
    for key in ("fwd", "bwd", "update_tail", *spans.SCOPES):
        assert layers[key] == 0.0


def _shardings():
    return {"tokens": SingleDeviceSharding(jax.devices()[0])}


def test_input_spans_are_read_apart_from_the_window(tmp_path):
    """Producer spans that start before the first ``bench.*`` annotation
    are in ``host_spans``; ``trace_reduce.load``'s ``host`` and ``window()``
    hold the harness's annotations alone."""
    made = threading.Event()

    def batch_fn(k):
        if k == 3:
            made.set()
        return {"tokens": np.full((2, 3), k, np.int32)}

    jax.profiler.start_trace(str(tmp_path))
    try:
        it = prefetch_to_device(batch_fn, _shardings(), 6)
        next(it)  # the producer starts before the window and fills the queue
        made.wait(timeout=30)
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.input_wait"):
                next(it)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    trace = tr.load(path)
    assert {n for _, _, n in trace.host} == {"bench.input_wait"}
    got = spans.host_spans(path)
    names = {n for _, _, n in got}
    assert names == {"repro.input.produce", "repro.input.put", "repro.input.wait"}
    assert sum(n == "repro.input.produce" for _, _, n in got) == 6
    lo, hi = tr.window(trace)
    assert (lo, hi) == (trace.host[0][0], max(e for _, e, _ in trace.host))
    assert min(s for s, _, _ in got) < lo  # the first batches, before it
    busy = spans.input_busy_seconds(got, lo, hi)
    inside = tr.length(tr.union(
        [(s, e) for s, e, n in got if n in spans.INPUT_SPANS], lo, hi))
    assert busy == pytest.approx(inside)
    assert 0 <= busy < hi - lo


def test_recorded_tpu_trace_has_no_program_spans():
    path = str(DATA / "tpu_v5e_small.xplane.pb")
    assert spans.host_spans(path) == []
    trace = tr.load(path)
    assert trace.host and tr.window(trace)[1] > tr.window(trace)[0]
