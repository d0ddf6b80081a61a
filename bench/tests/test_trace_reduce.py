"""The trace reduction on a small trace recorded on a TPU v5e: a jitted
program with a Pallas kernel (``%step.1``), run four times under the
harness's host annotations."""

from pathlib import Path

import pytest

import trace_reduce as tr

TRACE = Path(__file__).with_name("data") / "tpu_v5e_small.xplane.pb"
KERNEL = "step.1"  # the compiled program's tpu_custom_call


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(TRACE))


def test_planes_and_annotations(trace):
    assert sorted(trace.ops) == [0]
    names = {n for _, _, n in trace.host}
    assert {"bench.dispatch", "bench.device_wait", "bench.input_wait"} <= names
    assert all(o.end >= o.start for o in trace.ops[0])


def test_device_times_follow_the_host_enqueue(trace):
    lo, hi = tr.window(trace)
    first = min(o.start for o in trace.ops[0])
    assert first >= lo  # shifted onto the host clock: no op before dispatch


def test_busy_union_and_idle_share(trace):
    lo, hi = tr.window(trace)
    busy = tr.busy(trace, lo, hi)[0]
    total = sum(min(o.end, hi) - max(o.start, lo) for o in trace.ops[0]
                if o.end > lo and o.start < hi)
    assert 0 < busy <= total + 1e-12
    assert busy <= hi - lo
    idle = 1 - busy / (hi - lo)
    assert 0.5 < idle < 1.0  # four 0.1 ms programs in a window of ms


def test_kernel_found_by_name(trace):
    lo, hi = tr.window(trace)
    hits = [o for o in trace.ops[0] if o.instr == KERNEL]
    assert len(hits) == 4 and all(o.kind == "custom-call" for o in hits)
    secs = tr.kernel_seconds(trace, [KERNEL], lo, hi)[0]
    assert secs == pytest.approx(sum(o.end - o.start for o in hits), rel=1e-9)
    assert tr.kernel_seconds(trace, ["no-such-op"], lo, hi)[0] == 0.0


def test_idle_gaps_name_a_host_phase(trace):
    lo, hi = tr.window(trace)
    gaps = tr.idle_gaps(trace, 0, lo, hi)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    phases = {g[0] for g in gaps}
    assert phases <= {"bench.dispatch", "bench.device_wait", "bench.input_wait",
                      "host_other"}
    assert sum(g[1] for g in gaps) == pytest.approx(
        (hi - lo) - tr.busy(trace, lo, hi)[0], rel=1e-9)


def test_parse_name():
    assert tr.parse_name("%fusion.3 = f32[8]{0:T(256)} fusion(f32[8] %a), kind=kLoop") == (
        "fusion.3", "fusion")
    assert tr.parse_name("%copy-start = (bf16[2]{0:T(8)}, u32[]) copy-start(bf16[2] %x)") == (
        "copy-start", "copy-start")


def test_label():
    assert tr.label("%fusion.3 = f32[8,4]{1,0:T(8,128)} fusion(f32[8] %a), kind=kLoop, "
                    "calls=%f") == "fusion.3 f32[8,4] fusion:kLoop"


def test_intervals():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.union([(0, 10)], 2, 4) == [(2, 4)]
    assert tr.minus([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.minus([(0, 1), (4, 6)], [(0.5, 5)]) == [(0, 0.5), (5, 6)]


def test_self_times_subtract_children():
    ops = [tr.Op(0.0, 10.0, "while", "while"), tr.Op(1.0, 3.0, "a", "fusion"),
           tr.Op(4.0, 5.0, "b", "fusion"), tr.Op(11.0, 12.0, "a", "fusion")]
    st = tr.self_times(ops, 0.0, 20.0)
    assert st == pytest.approx({"while": 7.0, "a": 3.0, "b": 1.0})


def test_collective_exposed():
    t = tr.Trace(ops={0: [tr.Op(0, 4, "fusion.1", "fusion"),
                          tr.Op(6, 7, "collective-permute-done", "collective-permute-done")]},
                 async_ops={0: [tr.Op(2, 8, "collective-permute-start", "collective-permute-start")]},
                 host=[(0, 10, "bench.dispatch")])
    flight, exposed = tr.collective(t, "collective-permute", 0, 10)[0]
    assert flight == pytest.approx(6.0) and exposed == pytest.approx(4.0)
