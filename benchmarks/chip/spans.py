"""The program's own spans in a traced run, for the per-layer metrics of
the serving step.

The serving path writes its spans into the profiler's trace
(``repro.serve.scheduler`` and ``repro.serve.engine`` name them): on the
device timeline's clock, each with its attrs as the event's stats.  This
module reads the ``/host:`` events named ``serve.*`` as
``(name, start_ns, dur_ns, attrs)``, finds each span's parent (spans of
one thread nest), and reduces a window's spans to the metrics' numbers.

A reader gets the run's record, which holds the trace that ``trace.load``
made (the benchmark's ``bench.*`` spans only) and its window, but not the
file: :func:`of_record` reads the file under the temporary directory
whose ``bench.window`` is the record's window (``run.main`` writes each
traced run's trace there).  Where the trace holds no program span, as
with a program that writes none, every reduction gives None.
"""
from __future__ import annotations

import glob
import os
import statistics
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace

Span = Tuple[str, float, float, dict]  # (name, start_ns, dur_ns, attrs)

PREFIX = "serve."
#: the directories ``run.main`` traces into
TRACE_DIRS = "bench-trace-*"


def load(path: str) -> Tuple[List[Span], Optional[Tuple[float, float]]]:
    """(the program's spans, the ``bench.window`` span's (start_ns,
    end_ns) or None) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    spans.append((name, float(e.start_ns), float(e.duration_ns),
                                  dict(e.stats)))
                elif name == trace.WINDOW_SPAN:
                    window = (float(e.start_ns), float(e.start_ns + e.duration_ns))
    return spans, window


def of_record(rec) -> List[Span]:
    """The program's spans of ``rec``'s traced run that lie wholly in its
    window, in order of start; empty where there are none.  Read once per
    record and kept in its ``extra``."""
    if rec.trace_window is None:
        return []
    if "program_spans" not in rec.extra:
        window = tuple(rec.trace_window)
        found: List[Span] = []
        pattern = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**", "*.xplane.pb")
        for path in sorted(glob.glob(pattern, recursive=True), key=_mtime, reverse=True):
            try:
                spans, win = load(path)
            except (OSError, RuntimeError, ValueError):  # another run's file, gone or half written
                continue
            if win == window:
                found = spans
                break
        rec.extra["program_spans"] = within(found, window)
    return rec.extra["program_spans"]


def _mtime(path: str) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:
        return 0.0


def within(spans: List[Span], window: Tuple[float, float]) -> List[Span]:
    lo, hi = window
    return sorted((s for s in spans if s[1] >= lo and s[1] + s[2] <= hi),
                  key=lambda s: (s[1], -s[2]))


def children(spans: List[Span]) -> Dict[int, List[Span]]:
    """Each span's direct children, by the span's index in ``spans``
    (sorted by start, longer first); the roots under -1."""
    out: Dict[int, List[Span]] = {-1: []}
    stack: List[int] = []
    for i, (_, s, d, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] + spans[stack[-1]][2] < s + d:
            stack.pop()
        out.setdefault(stack[-1] if stack else -1, []).append(spans[i])
        out[i] = []
        stack.append(i)
    return out


def _descendants(spans: List[Span], i: int) -> List[Span]:
    end = spans[i][1] + spans[i][2]
    out = []
    for x in spans[i + 1:]:
        if x[1] >= end:
            break
        out.append(x)
    return out


def _steps(spans: List[Span]):
    return [i for i, x in enumerate(spans) if x[0] == "serve.step"]


def admit_ms(spans: List[Span]) -> Optional[float]:
    """Median ``serve.admit`` duration."""
    d = [d for n, _, d, _ in spans if n == "serve.admit"]
    return statistics.median(d) / 1e6 if d else None


def decode_ms(spans: List[Span]) -> Optional[float]:
    """Median, over the steps that decode, of the step's own
    ``serve.decode_step`` and ``serve.sample``: from the decode's dispatch
    until its tokens are on the host."""
    kids = children(spans)
    per = []
    for i in _steps(spans):
        own = {n: d for n, _, d, _ in kids[i] if n in ("serve.decode_step", "serve.sample")}
        if len(own) == 2:
            per.append(sum(own.values()))
    return statistics.median(per) / 1e6 if per else None


def host_ms(spans: List[Span]) -> Optional[float]:
    """Median, over the steps, of a step's duration less the
    ``serve.sample`` spans inside it: the host's own work in a step."""
    per = [spans[i][2] - sum(d for n, _, d, _ in _descendants(spans, i) if n == "serve.sample")
           for i in _steps(spans)]
    return statistics.median(per) / 1e6 if per else None


def prefill_pad_share(spans: List[Span]) -> Optional[float]:
    """Share of the prefills' positions that are bucket padding:
    100 × (Σ bucket − Σ prompt_len) / Σ bucket over the admissions."""
    admits = [a for n, _, _, a in spans if n == "serve.admit"]
    total = sum(a["bucket"] for a in admits)
    if not total:
        return None
    return 100.0 * (total - sum(a["prompt_len"] for a in admits)) / total


def slowest_steps(spans: List[Span], n: int = 5) -> List[list]:
    """The ``n`` longest ``serve.step``s as [ms, attrs, [child, ms,
    attrs]...], longest first."""
    kids = children(spans)
    out = []
    for i in sorted(_steps(spans), key=lambda i: -spans[i][2])[:n]:
        out.append([round(spans[i][2] / 1e6, 3), spans[i][3],
                    [[c, round(d / 1e6, 3), a] for c, _, d, a in kids[i]]])
    return out


def innermost(events: List[trace.Event]) -> List[Tuple[float, float, str]]:
    """The host's timeline as (start_ns, end_ns, name of the innermost
    span open then), from spans that nest; uncovered time is left out."""
    points = sorted({x for _, s, d in events for x in (s, s + d)})
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack, i = [], [], 0
    for p, q in zip(points, points[1:]):
        stack = [x for x in stack if x[1] > p]
        while i < len(ev) and ev[i][1] <= p:
            if ev[i][1] + ev[i][2] > p:
                stack.append((ev[i][0], ev[i][1] + ev[i][2]))
            i += 1
        if stack:
            out.append((p, q, stack[-1][0]))
    return out


def idle_by_innermost(tr, window: Tuple[float, float],
                      events: List[trace.Event]) -> List[list]:
    """The window's idle time on the device, each instant put down to the
    innermost host span open then (or ``"none"``): [name, seconds],
    largest first, averaged over the devices.  Unlike ``trace.idle_gaps``,
    which gives each whole gap to one span, a gap that crosses the end of
    one step into the next is split among the spans it crosses."""
    lo, hi = window
    timeline = innermost([e for e in events if e[0] != trace.WINDOW_SPAN])
    tot: Dict[str, float] = {}
    for ops in tr.device_ops.values():
        busy = trace.union(trace.clip(ops, window))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        j = 0
        for a, b in zip(edges[0::2], edges[1::2]):
            covered = 0.0
            while j < len(timeline) and timeline[j][1] <= a:
                j += 1
            k = j
            while k < len(timeline) and timeline[k][0] < b:
                s, e, name = timeline[k]
                part = min(b, e) - max(a, s)
                if part > 0:
                    tot[name] = tot.get(name, 0.0) + part / 1e9
                    covered += part
                k += 1
            if b - a > covered:
                tot["none"] = tot.get("none", 0.0) + (b - a - covered) / 1e9
    k = len(tr.device_ops) or 1
    return [[name, secs / k] for name, secs in sorted(tot.items(), key=lambda kv: -kv[1])]


def report(rec, spans: List[Span]) -> None:
    """On standard error: the slowest steps with their children, and the
    window's idle time by program or benchmark span, whole gaps by the
    span that covers most of each (``trace.idle_gaps``) and each instant
    by the innermost span (:func:`idle_by_innermost`)."""
    print(f"slowest serve.step (ms, attrs, children): {slowest_steps(spans)}",
          file=sys.stderr)
    if rec.trace is not None and rec.trace.device_ops:
        ev = list(rec.trace.host_spans) + [(n, s, d) for n, s, d, _ in spans]
        gaps = trace.idle_gaps(rec.trace, rec.trace_window, spans=ev)
        print(f"idle gaps by span (s): {gaps}", file=sys.stderr)
        inner = idle_by_innermost(rec.trace, rec.trace_window, ev)
        print(f"idle time by innermost span (s): {inner}", file=sys.stderr)
