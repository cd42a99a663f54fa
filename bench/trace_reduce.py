"""Reduce a profiler trace (``.xplane.pb``) to busy time, idle gaps and
time per device operation and per program.

Device planes are the ``/device:TPU:<n>`` planes; on each, the ``XLA
Ops`` line holds one event per operation run and the ``XLA Modules``
line one per program run.  The window is the host span
``bench.window``, which the harness opens just after the profiler
starts and closes just before it stops.  Busy time is the union of the
operations' intervals inside the window; every interval of the window
that it leaves is an idle gap, labelled with the host span
(``engine.step``, ``server.submit``, ``client.receive``) that overlaps
it most (when that span covers at least half of the gap).

An operation is keyed by its HLO instruction's name: a TPU trace names
its events by the instruction's whole text (``%fusion.12 = bf16[...]
fusion(...)``), of which the name is what precedes `` = ``, without the
``%``.  A program's runs are keyed by its name without the program id that a
device trace may append in parentheses (``jit_eval(1234)``).  Programs
that share a name (the engine jits its prefill and decode steps from
functions of one name) are told apart by the operations
that run inside them: a run of program ``p`` in which an operation
matching the mark ``label``'s pattern ran is counted as ``p [label]``.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = r"^XLA Ops$"
MODULE_LINE = r"^XLA Modules$"
HOST_PLANE = r"^/host:CPU$"
PROGRAM_ID = r"\s*\(\d+\)$"


def op_name(event_name: str) -> str:
    """``fusion.12`` for ``%fusion.12 = bf16[8] fusion(...)``; a name
    that is not an instruction's text is kept as it is."""
    return event_name.split(" = ", 1)[0].lstrip("%")
NO_SPAN = "no span"

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    """What a trace says, in seconds; averaged over the device planes
    where it is a time, summed where it is a total."""

    window_s: float
    busy_s: float
    devices: int
    ops: Dict[str, float]                    # name -> device seconds
    modules: Dict[str, Tuple[int, float]]    # name -> (runs, seconds)
    gaps: List[Tuple[str, float]]            # (host span, seconds)

    def idle_by_span(self) -> Dict[str, Tuple[int, float]]:
        out: Dict[str, Tuple[int, float]] = {}
        for name, s in self.gaps:
            n, t = out.get(name, (0, 0.0))
            out[name] = (n + 1, t + s)
        return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(iv[0], lo), min(iv[1], hi)
    return (a, b) if b > a else None


def complement(busy: Sequence[Interval], lo: float,
               hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (sorted, disjoint) leaves."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host span that overlaps ``gap`` most, if it covers at least
    half of it; ``NO_SPAN`` otherwise."""
    best, best_t = NO_SPAN, 0.5 * (gap[1] - gap[0])
    for name, a, b in spans:
        if b <= gap[0] or a >= gap[1]:
            continue
        t = min(b, gap[1]) - max(a, gap[0])
        if t > best_t:
            best, best_t = name, t
    return best


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def _marked(name: str, a: float, b: float, op_starts: List[float],
            op_events: List[Tuple[float, float, str]],
            marks: Dict[str, str]) -> str:
    """``name [label]`` if an op matching a mark ran in [a, b]."""
    i = bisect.bisect_left(op_starts, a)
    while i < len(op_events) and op_events[i][0] < b:
        for lab, pattern in marks.items():
            if re.search(pattern, op_events[i][2]):
                return f"{name} [{lab}]"
        i += 1
    return name


def reduce(path: str, spans: Sequence[str] = (), *,
           marks: Optional[Dict[str, str]] = None,
           device_plane: str = DEVICE_PLANE, op_line: str = OP_LINE,
           module_line: str = MODULE_LINE,
           host_plane: str = HOST_PLANE) -> Summary:
    """Reduce the trace at ``path``; ``spans`` name the host spans that
    label idle gaps, ``marks`` map a label to an op-name pattern."""
    marks = marks or {}
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    host = [p for p in planes if re.search(host_plane, p.name)]
    span_events = []
    window: Optional[Interval] = None
    for p in host:
        for line in p.lines:
            for name, a, b in _events(line):
                if name == WINDOW_SPAN:
                    window = (a, b)
                elif name in spans:
                    span_events.append((name, a, b))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = window
    devices = [p for p in planes if re.search(device_plane, p.name)]
    if not devices:
        raise ValueError(f"no device plane matches {device_plane!r}")
    ops: Dict[str, float] = {}
    modules: Dict[str, Tuple[int, float]] = {}
    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    for p in devices:
        intervals, op_events, runs = [], [], []
        for line in p.lines:
            is_op = re.search(op_line, line.name)
            is_mod = re.search(module_line, line.name)
            if not (is_op or is_mod):
                continue
            for name, a, b in _events(line):
                iv = clip((a, b), lo, hi)
                if iv is None:
                    continue
                if is_op:
                    name = op_name(name)
                    intervals.append(iv)
                    op_events.append((iv[0], iv[1], name))
                    ops[name] = ops.get(name, 0.0) + iv[1] - iv[0]
                else:
                    runs.append((re.sub(PROGRAM_ID, "", name), iv[0],
                                 iv[1]))
        op_events.sort()
        op_starts = [e[0] for e in op_events]
        for name, a, b in runs:
            key = _marked(name, a, b, op_starts, op_events, marks)
            n, s = modules.get(key, (0, 0.0))
            modules[key] = (n + 1, s + b - a)
        busy = union(intervals)
        busy_total += sum(b - a for a, b in busy)
        gaps += [(label(g, span_events), g[1] - g[0])
                 for g in complement(busy, lo, hi)]
    return Summary(window_s=hi - lo, busy_s=busy_total / len(devices),
                   devices=len(devices), ops=ops, modules=modules,
                   gaps=gaps)
