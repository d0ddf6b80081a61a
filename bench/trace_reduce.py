"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The TPU's planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per executed HLO instruction, named by the instruction's text
(``%fusion.12 = bf16[...] fusion(...)``), and ``Async XLA Ops`` the spans
of asynchronous ones (copies, collective-permutes) from start to done.
The host plane ``/host:CPU`` holds the harness's own ``bench.*``
annotations and the runtime's ``DoEnqueueProgram`` events.

Device times are shifted onto the host's clock: by the least amount that
puts no program's start before the host enqueued it (the two carry the
same ``run_id``).  Busy time is the union of the op intervals; a kernel
is found by the instruction names that the compiled program gives it.
"""

from __future__ import annotations

import dataclasses
import re
import warnings

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OP_KIND = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
FUSION_KIND = re.compile(r"kind=(k\w+)")


@dataclasses.dataclass
class Op:
    start: float  # seconds, host clock
    end: float
    instr: str  # HLO instruction name, without the "%"
    kind: str  # HLO opcode
    label: str = ""  # instruction, result type and opcode, for reports


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Op]]  # device id -> ops, by start
    async_ops: dict[int, list[Op]]
    host: list[tuple[float, float, str]]  # bench.* annotations


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in ev.stats}


def parse_name(name: str) -> tuple[str, str]:
    """(instruction, opcode) of an ``XLA Ops`` event name."""
    head, _, rest = name.partition(" = ")
    m = OP_KIND.search(rest)
    return head.strip().lstrip("%"), (m.group(1) if m else "")


def label(name: str) -> str:
    """``instruction result-type opcode[:fusion kind]`` of an event name."""
    instr, kind = parse_name(name)
    rest = name.partition(" = ")[2]
    m = OP_KIND.search(rest)
    result = re.sub(r"\{[^}]*\}", "", rest[: m.start()] if m else rest).strip()
    fk = FUSION_KIND.search(rest)
    return f"{instr} {result[:60]} {kind}" + (f":{fk.group(1)}" if fk else "")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[int, list[Op]] = {}
    async_ops: dict[int, list[Op]] = {}
    modules: dict[int, list[tuple[float, int]]] = {}
    host, enqueue = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[dev] = [(ev.start_ns * 1e-9, _stats(ev).get("run_id"))
                                    for ev in line.events]
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                dst = ops if line.name == "XLA Ops" else async_ops
                evs = dst.setdefault(dev, [])
                for ev in line.events:
                    instr, kind = parse_name(ev.name)
                    t = ev.start_ns * 1e-9
                    evs.append(Op(t, t + ev.duration_ns * 1e-9, instr, kind,
                                  label(ev.name)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    t = ev.start_ns * 1e-9
                    if ev.name.startswith("bench."):
                        host.append((t, t + ev.duration_ns * 1e-9, ev.name))
                    elif ev.name == "DoEnqueueProgram":
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            enqueue.setdefault(rid, t)
    for dev, mods in modules.items():
        shift = max([enqueue[r] - t for t, r in mods if r in enqueue] + [0.0])
        for lst in (ops.get(dev, []), async_ops.get(dev, [])):
            for op in lst:
                op.start += shift
                op.end += shift
    for lst in list(ops.values()) + list(async_ops.values()):
        lst.sort(key=lambda o: o.start)
    host.sort()
    return Trace(ops=ops, async_ops=async_ops, host=host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals, lo: float = -1e300, hi: float = 1e300) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list[tuple[float, float]]:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# what the metrics read
# ---------------------------------------------------------------------------


def window(tr: Trace) -> tuple[float, float]:
    """The traced window: first to last ``bench.*`` host annotation."""
    if not tr.host:
        raise ValueError("the trace holds no bench.* annotation")
    return tr.host[0][0], max(e for _, e, _ in tr.host)


def busy(tr: Trace, lo: float, hi: float) -> dict[int, float]:
    """Seconds in [lo, hi] in which an op ran, per device."""
    return {d: length(union(((o.start, o.end) for o in ops), lo, hi))
            for d, ops in tr.ops.items()}


def kernel_seconds(tr: Trace, names, lo: float, hi: float) -> dict[int, float]:
    """Device seconds of the ops named ``names``, per device."""
    names = set(names)
    return {d: length(union(((o.start, o.end) for o in ops if o.instr in names), lo, hi))
            for d, ops in tr.ops.items()}


def mean(per_device: dict) -> float:
    """Mean over the devices of a per-device number."""
    return sum(per_device.values()) / max(len(per_device), 1)


def collective(tr: Trace, kind: str, lo: float, hi: float) -> dict[int, tuple[float, float]]:
    """Per device: (seconds a ``kind`` collective is in flight, seconds of
    that during which no other op runs)."""
    out = {}
    for d, ops in tr.ops.items():
        spans = [(o.start, o.end) for o in tr.async_ops.get(d, []) if kind in o.instr]
        spans += [(o.start, o.end) for o in ops if o.kind == kind]
        flight = union(spans, lo, hi)
        other = union(((o.start, o.end) for o in ops if kind not in o.instr), lo, hi)
        out[d] = (length(flight), length(minus(flight, other)))
    return out


def self_times(ops: list[Op], lo: float, hi: float) -> dict[str, float]:
    """Seconds per instruction, an enclosing op's time less its children's."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [op, child seconds]

    def close(entry):
        op, child = entry
        s, e = max(op.start, lo), min(op.end, hi)
        if e > s:
            out[op.instr] = out.get(op.instr, 0.0) + max(e - s - child, 0.0)
        if stack:
            stack[-1][1] += max(e - s, 0.0)

    for op in ops:
        while stack and stack[-1][0].end <= op.start:
            close(stack.pop())
        stack.append([op, 0.0])
    while stack:
        close(stack.pop())
    return out


def idle_gaps(tr: Trace, device: int, lo: float, hi: float) -> list[tuple[str, float]]:
    """Idle gaps of one device in [lo, hi], each named by the host
    annotation open at its middle, longest first."""
    busy_iv = union(((o.start, o.end) for o in tr.ops.get(device, [])), lo, hi)
    gaps = minus([(lo, hi)], busy_iv)
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        phase = "host_other"
        for hs, he, name in tr.host:
            if hs <= mid < he and name != "bench.step":
                phase = name
        out.append((phase, e - s))
    out.sort(key=lambda g: -g[1])
    return out
