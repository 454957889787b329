"""Reduction of a profiler trace (``.xplane.pb``) to device times.

Device planes are the ``/device:TPU:<n>`` planes; on each, the "XLA Ops"
line holds one event per operation that ran and the "XLA Modules" line one
per program execution, both carrying the ``program_id`` of their program.
The window is the interval between the harness's ``bench.window_start``
and ``bench.window_end`` host spans.  Busy time is the union of operation
intervals inside the window, averaged over the chips; an idle gap is named
by the benchmark host span that covers most of it, or ``program`` where
more of it lies outside every benchmark span than inside that one (the
executor's own code between step-function calls).

The program's jitted steps are ``functools.partial`` objects, so every one
of them is named ``jit__unknown`` in a trace, and a TPU op event's name is
its HLO instruction's text ("%branch_0_fun.7 = bf16[...] custom-call(...),
custom_call_target=..."), with no program id.  A program execution is
therefore recognised by what ran inside it (the decode step runs the paged
kernel's ``tpu_custom_call``) or by the harness step it fell in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_START = "bench.window_start"
WINDOW_END = "bench.window_end"
SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    """One device event: ``text`` holds its string stats (the HLO op, its
    source op name), for matching what the name alone does not say."""
    name: str
    start: float
    end: float
    program: Optional[int] = None
    text: str = ""


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (merged, sorted) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# control flow: a loop or branch op spans the operations of its body
CONTAINERS = ("while", "conditional", "call")
_KIND = re.compile(r"[\]\})] ([a-z][a-z0-9-]*)\(")


def op_kind(name: str) -> str:
    """The HLO opcode of a TPU op event, whose name is the instruction's
    text ("%fusion.3 = bf16[...] fusion(...), ..."); '' if none."""
    head, sep, rest = name.partition(" = ")
    m = _KIND.search(rest) if sep else None
    return m.group(1) if m else ""


def short_name(name: str) -> str:
    """"%fusion.3 fusion" for an HLO instruction's text; other names as
    they are."""
    head, sep, _rest = name.partition(" = ")
    return f"{head} {op_kind(name)}".strip() if sep else name


def _event(e) -> Event:
    program, text = None, []
    for key, value in e.stats:
        if key == "program_id":
            program = int(value)
        elif isinstance(value, str):
            text.append(value)
    return Event(e.name, e.start_ns, e.end_ns, program, " ".join(text))


class Trace:
    """One traced window, reduced.  Times are in nanoseconds of the
    trace's own clock; ``devices`` maps each chip's plane to its
    {line: events}, clipped to the window."""

    def __init__(self, spans: List[Tuple[str, float, float]],
                 devices: Dict[str, Dict[str, List[Event]]]):
        self.spans = spans
        starts = [s for n, s, _e in spans if n == WINDOW_START]
        ends = [s for n, s, _e in spans if n == WINDOW_END]
        if not starts or not ends:
            raise ValueError("trace holds no bench.window_start/_end spans")
        self.t0, self.t1 = min(starts), max(ends)
        self.devices = {
            plane: {line: [Event(e.name, max(e.start, self.t0),
                                 min(e.end, self.t1), e.program, e.text)
                           for e in evs if e.end > self.t0
                           and e.start < self.t1]
                    for line, evs in lines.items()}
            for plane, lines in devices.items()}

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        spans: List[Tuple[str, float, float]] = []
        devices: Dict[str, Dict[str, List[Event]]] = {}
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                devices[plane.name] = {
                    line.name: [_event(e) for e in line.events]
                    for line in plane.lines
                    if line.name in (OPS_LINE, MODULES_LINE)}
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.name, e.start_ns, e.end_ns))
        return cls(spans, devices)

    @classmethod
    def from_dir(cls, trace_dir: str) -> Optional["Trace"]:
        found = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return cls.from_file(found[-1]) if found else None

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def events(self, line: str) -> List[Event]:
        """Every chip's events on ``line``, inside the window."""
        return [e for d in self.devices.values() for e in d.get(line, [])]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [sum(e - s for s, e in union((ev.start, ev.end)
                                           for ev in d.get(OPS_LINE, [])))
               for d in self.devices.values()]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def leaf_ops(self) -> List[Event]:
        """Operations that are not loops or branches around others."""
        return [e for e in self.events(OPS_LINE)
                if op_kind(e.name) not in CONTAINERS]

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per operation (short name), summed over the
        chips; loop and branch ops are left out, their bodies count."""
        out: Dict[str, float] = defaultdict(float)
        for e in self.leaf_ops():
            out[short_name(e.name)] += (e.end - e.start) * 1e-9
        return dict(out)

    def matching_ops(self, *needles: str) -> List[Event]:
        """Leaf operations whose name or stats contain any of
        ``needles``."""
        return [e for e in self.leaf_ops()
                if any(n in e.name or n in e.text for n in needles)]

    def programs_running(self, *needles: str) -> Set[int]:
        """Ids of the programs that ran an operation matching one of
        ``needles``."""
        return {e.program for e in self.matching_ops(*needles)
                if e.program is not None}

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps of the first chip, longest first, each named by the
        benchmark host span that covers most of it."""
        first = next(iter(self.devices.values()), {})
        busy = union((e.start, e.end) for e in first.get(OPS_LINE, []))
        named = [(n, s, e) for n, s, e in self.spans
                 if n not in (WINDOW_START, WINDOW_END)]
        cover = union((s, e) for _n, s, e in named)
        out = []
        for g in gaps(busy, self.t0, self.t1):
            best, name = 0.0, "program"
            for n, s, e in named:
                ov = overlap(g, (s, e))
                if ov > best:
                    best, name = ov, n
            uncovered = (g[1] - g[0]) - sum(overlap(g, c) for c in cover)
            if uncovered > best:
                name = "program"
            out.append((name, (g[1] - g[0]) * 1e-9))
        out.sort(key=lambda x: -x[1])
        return out


def step_programs(trace: Trace, steps: Sequence, host_t0: float,
                  kernel: Sequence[str]) -> Dict[str, List[Event]]:
    """Program executions of the window split into "decode" (executions
    during which the kernel, matched by ``kernel``, ran, or of a program
    known to run it), "prefill" (other programs that ran during a
    step-function call that admitted requests) and "other".  ``steps``
    are the harness's step records, on the host clock whose reading at
    the window's start span is ``host_t0``."""
    decode = trace.programs_running(*kernel)
    kernel_starts = sorted(e.start for e in trace.matching_ops(*kernel))
    offset = trace.t0 - host_t0 * 1e9
    admitting = [(s.t_call * 1e9 + offset, s.t_return * 1e9 + offset)
                 for s in steps if s.prefill_rows]
    out: Dict[str, List[Event]] = {"decode": [], "prefill": [], "other": []}
    for run in trace.events(MODULES_LINE):
        i = bisect.bisect_left(kernel_starts, run.start)
        holds_kernel = i < len(kernel_starts) and kernel_starts[i] <= run.end
        if run.program in decode or holds_kernel:
            out["decode"].append(run)
        elif any(lo <= run.start <= hi for lo, hi in admitting):
            out["prefill"].append(run)
        else:
            out["other"].append(run)
    return out
