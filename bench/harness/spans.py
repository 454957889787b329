"""The program's host spans in a profiler trace, reduced.

The served path writes ``repro.<layer>.<phase>`` host spans
(``repro.tracing.span``) and the harness its ``bench.*`` spans, all on the
thread that drives the loop (the thread of ``bench.step_fn``).  ``Trace``
(``trace.py``) keeps only the ``bench.*`` spans, without their thread or
stats; this module reads every ``repro.*`` and ``bench.*`` host span of
the same ``.xplane.pb`` with its thread line and stats, and reduces them
against a ``Trace`` of the file:

- the first chip's idle time in the window, split by the innermost span
  on the driving thread that it fell in;
- the spans' stats (the decoder's launch counters among them) summed
  over the spans that started in the window.

Each reduction returns ``None`` where the trace holds no program spans.
``bench/run.py`` deletes its trace once ``Trace`` has read it, so these
readings come from ``bench/record_trace.py``, which keeps the file.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .trace import OPS_LINE, Trace, gaps, overlap, union

PREFIXES = ("repro.", "bench.")
DRIVING_SPAN = "bench.step_fn"          # marks the driving thread
LAUNCH = "repro.decoder.launch"
DECODE = ("decode_step",)
PREFILL = ("prefill_into_pages", "prefill_into_slots")


@dataclass
class Span:
    """One host span: nanoseconds of the trace's clock, its thread line,
    and its stats (ints or short strings)."""
    name: str
    start: float
    end: float
    line: str = ""
    stats: Dict[str, object] = field(default_factory=dict)


def read(path: str) -> List[Span]:
    """Every ``repro.*`` and ``bench.*`` host span of a trace file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(Span(e.name, e.start_ns, e.end_ns, line.name,
                                dict(e.stats))
                           for e in line.events
                           if e.name.startswith(PREFIXES))
    return out


def driving(spans: Sequence[Span]) -> List[Span]:
    """The spans on the thread of ``bench.step_fn``."""
    lines = {s.line for s in spans if s.name == DRIVING_SPAN}
    return [s for s in spans if s.line in lines]


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted ``(start, end, name)`` segments covering the spans
    of one thread, each named by the innermost span open over it (spans
    of one thread nest; a child is clipped to its parent)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []     # (end, name) of open spans
    t = 0.0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        close_until(s.start)
        if stack and s.start > t:
            out.append((t, s.start, stack[-1][1]))
        t = s.start
        stack.append((min(s.end, stack[-1][0]) if stack else s.end, s.name))
    close_until(float("inf"))
    return out


def idle_gaps_by_span(trace: Trace, spans: Sequence[Span]
                      ) -> List[Tuple[float, Dict[str, float]]]:
    """Each idle gap of the first chip in the window: its seconds, and the
    seconds of it spent in each innermost span of the driving thread
    (time outside every such span is left out)."""
    first = next(iter(trace.devices.values()), {})
    busy = union((e.start, e.end) for e in first.get(OPS_LINE, []))
    segs = innermost(driving(spans))
    starts = [s for s, _e, _n in segs]
    out = []
    for g in gaps(busy, trace.t0, trace.t1):
        split: Dict[str, float] = defaultdict(float)
        i = max(bisect.bisect_right(starts, g[0]) - 1, 0)
        while i < len(segs) and segs[i][0] < g[1]:
            ov = overlap(g, segs[i][:2])
            if ov > 0:
                split[segs[i][2]] += ov * 1e-9
            i += 1
        out.append(((g[1] - g[0]) * 1e-9, dict(split)))
    return out


def idle_by_span(trace: Trace, spans: Sequence[Span]) -> Dict[str, float]:
    """Seconds of the first chip's idle time in the window, by the
    innermost span of the driving thread it fell in."""
    out: Dict[str, float] = defaultdict(float)
    for _secs, split in idle_gaps_by_span(trace, spans):
        for name, secs in split.items():
            out[name] += secs
    return dict(out)


def idle_share(trace: Trace, spans: Sequence[Span],
               prefix: str) -> Optional[float]:
    """Percent of the window in which the first chip was idle while the
    driving thread's innermost span started with ``prefix``
    (``repro.executor.`` or ``repro.decoder.``)."""
    if not (trace.devices and trace.window_s > 0
            and any(s.name.startswith("repro.") for s in spans)):
        return None
    idle = idle_by_span(trace, spans)
    return 100.0 * sum(v for n, v in idle.items()
                       if n.startswith(prefix)) / trace.window_s


def launches(trace: Trace, spans: Sequence[Span],
             programs: Sequence[str]) -> List[Span]:
    """The decoder's launches of ``programs`` that started in the
    window."""
    return [s for s in spans if s.name == LAUNCH
            and s.stats.get("program") in programs
            and trace.t0 <= s.start <= trace.t1]


def stat_sums(trace: Trace, spans: Sequence[Span]
              ) -> Dict[str, Dict[str, int]]:
    """For the ``repro.*`` spans that started in the window, by name (a
    launch by ``name:program``): how many there were, and the sum of each
    of their integer stats."""
    out: Dict[str, Dict[str, int]] = {}
    for s in spans:
        if s.name.startswith("repro.") and trace.t0 <= s.start <= trace.t1:
            key = s.name
            if "program" in s.stats:
                key = f"{s.name}:{s.stats['program']}"
            sums = out.setdefault(key, {"count": 0})
            sums["count"] += 1
            for stat, value in s.stats.items():
                if isinstance(value, int):
                    sums[stat] = sums.get(stat, 0) + value
    return out


def _ratio(runs: Sequence[Span], part: str, whole: str) -> Optional[float]:
    den = sum(int(s.stats.get(whole, 0)) for s in runs)
    if den <= 0:
        return None
    return 100.0 * sum(int(s.stats.get(part, 0)) for s in runs) / den


def kv_page_use_share(trace: Trace,
                      spans: Sequence[Span]) -> Optional[float]:
    """Pages in use over pages reserved (trash page left out), summed
    over the window's decode launches."""
    return _ratio(launches(trace, spans, DECODE), "pages_in_use",
                  "pages_reserved")


def prefill_useful_share(trace: Trace,
                         spans: Sequence[Span]) -> Optional[float]:
    """Prompt tokens over padded positions (rows bucket x tokens bucket),
    summed over the window's prefill launches."""
    return _ratio(launches(trace, spans, PREFILL), "tokens",
                  "padded_tokens")
