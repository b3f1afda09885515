"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

What a TPU trace holds, as read from one by hand:

- each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` has one
  event per HLO instruction run on the device, named by the instruction's
  text (``%run.29 = s8[96,768]{...} custom-call(...), custom_call_target=
  "tpu_custom_call", ...``);
- a Pallas kernel is a ``custom-call`` whose target is ``tpu_custom_call``
  (the program's kernels carry no names of their own, so they are told
  apart by instruction name and operand shapes);
- the host is the plane ``/host:CPU``; the line of the calling thread
  (named after the process, ``python`` or ``python3``) holds the
  benchmark's ``TraceAnnotation`` around each call and JAX's own spans
  inside it (``shard_args`` for host-to-device uploads, ``PjitFunction``
  for the dispatch, ``np.asarray(jax.Array)`` for the fetch back).

All times share one clock in nanoseconds. The traced window runs from the
start of the first annotated call to the end of the last.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CALL_SPAN = "bench_call"
#: Idle time on no host span of the python thread: the program's own
#: Python and NumPy work between JAX calls.
UNSPANNED = "host python outside JAX spans"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device op events per chip, and the calling thread's host spans."""
    device_ops: Dict[str, List[Event]]
    python: List[Event]


@dataclasses.dataclass
class Summary:
    calls: int
    window_s: float
    busy_s: float            # per chip, averaged over chips
    kernel_s: float          # per chip, averaged
    kernel_count: float      # per chip, averaged
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    python: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(e.name, e.start_ns,
                                             e.duration_ns)
                                       for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [Event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
                if any(e.name == CALL_SPAN for e in events):
                    python = events
    return Trace(ops, python)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Disjoint sorted union of ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(iv, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


_SHAPE = re.compile(r"\b[a-z]+\d*\[[\d,]*\]")


def op_label(name: str) -> str:
    """Short stable label of a device op: instruction name, opcode and,
    for a kernel, the shapes of its operands after the arena."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:80]
    instr = head.lstrip("%")
    if KERNEL_MARK in rest:
        body = rest.split("custom-call(", 1)[-1].split(KERNEL_MARK, 1)[0]
        shapes = _SHAPE.findall(body)[1:]
        if len(shapes) > 2:
            shapes = [shapes[0], f"... {len(shapes)} weights"]
        return f"{instr} pallas({','.join(shapes)})"
    opcode = rest.split("(", 1)[0].split(" ")[-1]
    return f"{instr} {opcode}"


def _top_spans(python: Sequence[Event]) -> List[Event]:
    """The python thread's outermost spans inside the annotated calls."""
    spans = sorted((e for e in python if e.name != CALL_SPAN),
                   key=lambda e: (e.start_ns, -e.dur_ns))
    out: List[Event] = []
    for e in spans:
        if out and e.start_ns < out[-1].end_ns:
            continue
        out.append(e)
    return out


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   python: Sequence[Event]) -> Dict[str, float]:
    """Idle nanoseconds by what the host's python thread was in: the
    outermost JAX span overlapping each part of a gap, else
    :data:`UNSPANNED`."""
    spans = _top_spans(python)
    out: Dict[str, float] = collections.defaultdict(float)
    first = 0
    for gs, ge in sorted(gaps):
        while first < len(spans) and spans[first].end_ns <= gs:
            first += 1
        covered = 0.0
        for e in spans[first:]:
            if e.start_ns >= ge:
                break
            o = min(ge, e.end_ns) - max(gs, e.start_ns)
            out[e.name] += o
            covered += o
        if ge - gs - covered > 0:
            out[UNSPANNED] += ge - gs - covered
    return out


def summarise(trace: Trace, top: int = 10) -> Summary:
    calls = [e for e in trace.python if e.name == CALL_SPAN]
    if not calls or not trace.device_ops:
        raise ValueError("trace holds no annotated call or no device plane")
    lo = min(e.start_ns for e in calls)
    hi = max(e.end_ns for e in calls)
    busy = kernel = count = 0.0
    by_op: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for events in trace.device_ops.values():
        inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
        iv = union(clip([(e.start_ns, e.end_ns) for e in inside], lo, hi))
        busy += sum(e - s for s, e in iv)
        for e in inside:
            by_op[op_label(e.name)] += e.dur_ns
            if KERNEL_MARK in e.name:
                kernel += e.dur_ns
                count += 1
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for k, v in attribute_gaps(gaps, trace.python).items():
            idle[k] += v
    n = len(trace.device_ops)
    rank = lambda d: [[k, v / n / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Summary(len(calls), (hi - lo) / 1e9, busy / n / 1e9,
                   kernel / n / 1e9, count / n, rank(by_op), rank(idle))
