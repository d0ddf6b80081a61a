"""Device time by the program's own layers, read from the scopes it names.

The program wraps its layers in ``jax.named_scope``, and JAX writes each
operation's scope path, with the transformations around it, into the
compiled program's ``op_name`` metadata:

* ``model`` (the loss): its forward is ``jvp(model)/...``, its backward
  ``transpose(jvp(model))/...``, and remat's recompute of it has a
  ``rematted_computation`` segment;
* ``update_tail``: everything after the gradient (guard, planes, update
  kernel, gossip, metric reductions);
* ``attn_core``, ``lm_head``, ``plane_pack``, ``plane_unpack``: layers
  inside those, counted in whatever phase they run.

Each instruction of the compiled step is put in one phase (``PHASES``) and
in the scopes it runs under (``SCOPES``); a device op's time is its
exclusive time (``exclusive_times``: its self time where ops nest), so a
``while`` does not absorb its body, and the phases sum to the busy time.
An ``op_name`` that XLA merged from several operations (``a;b``) takes the
phase of the first of them that has one, and every scope of any.

On the host the input pipeline records ``repro.*`` spans in the same
profiler trace (``host_spans``); ``trace_reduce.load`` keeps only the
harness's ``bench.*`` annotations, so these are read from the file apart.

No metric of ``BENCHMARK.json`` reads this module yet: a traced run would
have to keep the compiled step's ``op_spans`` and the trace's
``host_spans`` beside its ``Trace`` (PERF.md, Open questions).
"""

from __future__ import annotations

import heapq
import math
import re

import trace_reduce

PHASES = ("fwd", "bwd", "recompute", "update_tail", "unattributed")
SCOPES = ("attn_core", "lm_head", "plane_pack", "plane_unpack")
INPUT_SPANS = ("repro.input.produce", "repro.input.put")

INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="((?:[^"\\]|\\.)*)"')
WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")


def op_spans(hlo_text: str) -> dict[str, str]:
    """Instruction name -> its ``op_name`` metadata, over a compiled
    program's text (instructions without one are left out)."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def segments(path: str) -> list[str]:
    """``path`` split at the slashes outside parentheses."""
    out, cur, depth = [], [], 0
    for ch in path:
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return out


def unwrap(segment: str) -> tuple[tuple[str, ...], str]:
    """(transformations around a segment, outermost first; the name inside):
    ``transpose(jvp(model))`` -> (("transpose", "jvp"), "model")."""
    wraps = []
    m = WRAPPED.match(segment)
    while m:
        wraps.append(m.group(1))
        segment = m.group(2)
        m = WRAPPED.match(segment)
    return tuple(wraps), segment


def _classify_path(path: str) -> tuple[str, set]:
    segs = [unwrap(s) for s in segments(path)]
    names = {name for _, name in segs}
    if "rematted_computation" in names:
        phase = "recompute"
    elif any(name == "model" and "transpose" in wraps for wraps, name in segs):
        phase = "bwd"
    elif "model" in names:
        phase = "fwd"
    elif "update_tail" in names:
        phase = "update_tail"
    else:
        phase = "unattributed"
    return phase, names & set(SCOPES)


def classify(op_name: str) -> tuple[str, frozenset]:
    """(phase, scopes) of one instruction's ``op_name``."""
    phase, scopes = "unattributed", set()
    for path in op_name.split(";"):
        p, s = _classify_path(path)
        if phase == "unattributed":
            phase = p
        scopes |= s
    return phase, frozenset(scopes)


def exclusive_times(ops, lo: float, hi: float) -> dict[str, float]:
    """Seconds per instruction in [lo, hi]: each instant in which an op runs
    goes to the op that started last among those running.  Where ops nest
    (a ``while`` and its body) that is ``trace_reduce.self_times``; where
    two overlap without nesting, the instant is counted once, so the times
    sum to the busy time."""
    out: dict[str, float] = {}
    order = sorted(range(len(ops)), key=lambda j: ops[j].start)
    running: list = []  # heap of (-start, end, position, instr)
    i, t = 0, -math.inf
    while i < len(order) or running:
        if not running:
            t = max(t, ops[order[i]].start)
        while i < len(order) and ops[order[i]].start <= t:
            o = ops[order[i]]
            heapq.heappush(running, (-o.start, o.end, i, o.instr))
            i += 1
        while running and running[0][1] <= t:
            heapq.heappop(running)
        if not running:
            continue
        _, end, _, instr = running[0]
        nxt = min(end, ops[order[i]].start if i < len(order) else math.inf)
        s, e = max(t, lo), min(nxt, hi)
        if e > s:
            out[instr] = out.get(instr, 0.0) + e - s
        t = nxt
    return out


def layer_seconds(trace, names: dict[str, str], lo: float,
                  hi: float) -> dict[str, float]:
    """Device seconds in [lo, hi] of each phase and scope, mean over the
    chips, with ``names`` the compiled step's ``op_spans``."""
    out = dict.fromkeys(PHASES + SCOPES, 0.0)
    for ops in trace.ops.values():
        for instr, secs in exclusive_times(ops, lo, hi).items():
            phase, scopes = classify(names.get(instr, ""))
            for key in (phase, *scopes):
                out[key] += secs / len(trace.ops)
    return out


def host_spans(path: str) -> list[tuple[float, float, str]]:
    """The program's ``repro.*`` host spans in an ``.xplane.pb``, on the
    host clock (the clock of ``trace_reduce.load``'s ``host``), by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    t = ev.start_ns * 1e-9
                    out.append((t, t + ev.duration_ns * 1e-9, ev.name))
    return sorted(out)


def input_busy_seconds(spans, lo: float, hi: float) -> float:
    """Host seconds in [lo, hi] in which the input producer makes or places
    a batch (``INPUT_SPANS``)."""
    iv = [(s, e) for s, e, name in spans if name in INPUT_SPANS]
    return trace_reduce.length(trace_reduce.union(iv, lo, hi))
