"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain events, ``(name, start_ns, dur_ns)``, all on
the profiler's one clock:

- device operations: the ``XLA Ops`` line of every ``/device:`` plane;
- host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans,
  whose names start with ``bench.``.

From them: the busy time (the union of the operations' intervals) and
the idle share of a window, the device operations that took most time,
and the longest idle gaps, each attributed to the host span that covers
most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, dur_ns)

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    """Device operations per device, and the host's spans."""

    device_ops: Dict[str, List[Event]]
    host_spans: List[Event]

    def window(self, name: str = WINDOW_SPAN) -> Tuple[float, float]:
        """(start_ns, end_ns) of the one host span ``name``."""
        got = [(s, s + d) for n, s, d in self.host_spans if n == name]
        if len(got) != 1:
            raise ValueError(f"expected one {name!r} span in the trace, found {len(got)}")
        return got[0]


def load(path: str) -> Trace:
    """Read ``.xplane.pb`` (a file, or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops=device_ops, host_spans=spans)


def clip(events: Iterable[Event], window: Tuple[float, float]) -> List[Event]:
    """Events cut to the window; those wholly outside it are dropped."""
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals of the events, sorted."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event], window: Tuple[float, float]) -> float:
    return sum(b - a for a, b in union(clip(events, window)))


def idle_share(trace: Trace, window: Tuple[float, float]) -> float:
    """1 − busy / window, averaged over the devices."""
    length = window[1] - window[0]
    shares = [1.0 - busy_ns(ev, window) / length for ev in trace.device_ops.values()]
    return sum(shares) / len(shares)


def mean_busy_s(trace: Trace, window: Tuple[float, float]) -> float:
    vals = [busy_ns(ev, window) for ev in trace.device_ops.values()]
    return sum(vals) / len(vals) / 1e9


_SUFFIX = re.compile(r"[.:]\d+$")


def short(name: str) -> str:
    """An operation's name without its numbering: a TPU trace names each
    operation by its HLO text (``%fusion.12 = f32[...] fusion(...)``)."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))


_CONTAINERS = ("while", "conditional", "call")
_CONTAINER_TEXT = re.compile(r" (while|conditional|call)\(")


def is_container(name: str) -> bool:
    """A control-flow operation, whose event spans the operations of its
    body (those are events of their own)."""
    return short(name) in _CONTAINERS or bool(_CONTAINER_TEXT.search(name))


def top_ops(trace: Trace, window: Tuple[float, float], n: int = 10) -> List[list]:
    """The ``n`` device operations (numbered copies merged, control flow
    left out) that took most time, as [name, seconds], averaged over the
    devices."""
    tot: Dict[str, float] = {}
    for events in trace.device_ops.values():
        for name, _, d in clip(events, window):
            if is_container(name):
                continue
            key = short(name)
            tot[key] = tot.get(key, 0.0) + d / 1e9
    k = len(trace.device_ops) or 1
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]


def idle_gaps(trace: Trace, window: Tuple[float, float],
              spans: Optional[List[Event]] = None, n: int = 10) -> List[list]:
    """Idle time between device operations, by what the host was doing:
    each gap goes to the innermost host span (other than the window's)
    that covers most of it, or to ``"none"``.  [name, seconds] summed per
    span name, the ``n`` largest, averaged over the devices."""
    spans = [s for s in (spans if spans is not None else trace.host_spans)
             if s[0] != WINDOW_SPAN]
    tot: Dict[str, float] = {}
    lo, hi = window
    for events in trace.device_ops.values():
        busy = union(clip(events, window))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            best = max(
                ((min(b, s + d) - max(a, s), -d, name) for name, s, d in spans),
                default=(0.0, 0.0, "none"),
            )
            best = best[2] if best[0] > 0 else "none"
            tot[best] = tot.get(best, 0.0) + (b - a) / 1e9
    k = len(trace.device_ops) or 1
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / k] for name, secs in ranked]

