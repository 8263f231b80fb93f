"""The telemetry hub: dual-clock spans, metric streams, progress events.

One :class:`TelemetryHub` per run fans structured events out to its sinks
(:mod:`repro.telemetry.sinks`).  Every event carries **wall** time (``t``,
monotonic seconds since the hub's epoch, read through the sanctioned
:mod:`repro.telemetry.clock` shim) and, when a simulator's
:class:`~repro.fed.sim.clock.VirtualClock` is attached, **virtual** time
(``tv``) — the dual-clock record that lets a Perfetto trace show both
what the host actually did and what the simulated fleet experienced.

API surface:

- ``with hub.span("round", round=r): ...`` — wall-duration span, also
  written into the profiler's trace (below);
- ``hub.span_at("client_round", tv0, tv1, client=c)`` — a span on the
  *virtual* clock with explicit endpoints (the async engine's
  dispatch→arrival client rounds, priced by the simulator);
- ``hub.counter(name, inc) / hub.gauge(name, value) / hub.hist(name,
  value)`` — metric samples;
- ``hub.progress(msg)`` — a human-facing progress line, rendered by
  :class:`~repro.telemetry.sinks.ConsoleSink` (the engines' old
  ``print()`` calls, now one event kind among the rest).

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name,
with its attrs as the event's stats, around the same interval: whoever
collects a profiler trace sees the program's spans on the device
timeline's own clock.  The annotation is inert (about a microsecond)
while no profiler collects.  Attrs known only when a span ends go in
through the ``set_metadata(**attrs)`` of what ``with hub.span(...) as sp``
binds.

The load-bearing invariant (pinned in ``tests/test_telemetry.py``):
telemetry **reads state and never writes it** — no RNG draws, no virtual
clock advances, no engine mutation — so a telemetry-enabled run is
bit-for-bit identical to a disabled one.  A disabled hub
(``enabled=False``, e.g. :data:`NULL_HUB`) builds no event: its
counters, gauges and hists return at once, and its spans are the
profiler annotation alone.  Only its progress lines still render, on
the :class:`~repro.telemetry.sinks.ConsoleSink` among its sinks (the
cost is pinned by ``benchmarks/bench_telemetry.py``).

Gauges and hists that carry a ``round=`` attr respect ``sample_every``:
only rounds divisible by the cadence are recorded — spans, counters and
progress are never sampled away.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional

from jax.profiler import TraceAnnotation

from repro.telemetry.clock import perf_seconds, wall_time
from repro.telemetry.sinks import ConsoleSink, make_sinks

_UNSET = object()


class _Span:
    """What ``with hub.span(...) as sp`` binds on an enabled hub:
    ``sp.set_metadata(**attrs)`` adds attrs to the sink event and to the
    annotation alike (a disabled hub binds the annotation itself, which
    has the same method)."""

    __slots__ = ("annotation", "attrs")

    def __init__(self, annotation, attrs):
        self.annotation, self.attrs = annotation, attrs

    def set_metadata(self, **attrs) -> None:
        self.attrs.update(attrs)
        self.annotation.set_metadata(**attrs)


class TelemetryHub:
    """Fan structured run events out to pluggable sinks; see module doc."""

    def __init__(
        self,
        sinks=(),
        *,
        enabled: bool = True,
        clock=None,
        sample_every: int = 1,
        meta: Optional[dict] = None,
    ):
        self.enabled = bool(enabled)
        self.sinks: List[object] = list(sinks)
        self.sample_every = max(int(sample_every), 1)
        self._clock = clock  # duck-typed: anything with a float `.now`
        self._seq = 0
        self._epoch = perf_seconds()
        if self.enabled and self.sinks:
            self._emit(
                "meta", "hub_start",
                attrs={"wall_epoch": wall_time(), **(meta or {})},
            )

    # -- clocks ------------------------------------------------------------

    def attach_clock(self, clock) -> None:
        """Attach a virtual clock (read-only: the hub only ever looks at
        ``clock.now``; advancing it stays the simulator's job)."""
        self._clock = clock

    def virtual_now(self) -> Optional[float]:
        return None if self._clock is None else float(self._clock.now)

    # -- emission core -----------------------------------------------------

    def _emit(self, kind, name, *, t=None, dur=None, tv=_UNSET, durv=None,
              value=None, attrs=None, sinks=None):
        event = {
            "kind": kind,
            "name": name,
            "t": (perf_seconds() - self._epoch) if t is None else float(t),
            "dur": dur,
            "tv": self.virtual_now() if tv is _UNSET else tv,
            "durv": durv,
            "value": value,
            "attrs": attrs or {},
            "seq": self._seq,
        }
        self._seq += 1
        for sink in self.sinks if sinks is None else sinks:
            sink.emit(event)

    def _sampled(self, attrs: dict) -> bool:
        r = attrs.get("round")
        if r is None or self.sample_every == 1:
            return True
        return int(r) % self.sample_every == 0

    # -- spans -------------------------------------------------------------

    @contextlib.contextmanager
    def _span_cm(self, name, attrs):
        t0 = perf_seconds()
        tv0 = self.virtual_now()
        annotation = TraceAnnotation(name, **attrs)
        try:
            with annotation:
                yield _Span(annotation, attrs)
        finally:
            self._emit(
                "span", name,
                t=t0 - self._epoch,
                dur=perf_seconds() - t0,
                tv=tv0,
                attrs=attrs,
            )

    def span(self, name: str, **attrs):
        """Context manager timing a wall-clock span (virtual time is
        stamped at entry for context; virtual *durations* come from
        :meth:`span_at`, which the simulators price explicitly), written
        into the profiler's trace as well; a disabled hub's span is that
        annotation alone."""
        if not self.enabled:
            return TraceAnnotation(name, **attrs)
        return self._span_cm(name, attrs)

    def span_at(self, name: str, tv_start: float, tv_end: float, **attrs):
        """Record a completed span on the **virtual** clock with explicit
        endpoints — dispatch→arrival client rounds, straggler barriers —
        attributed to ``attrs['client']``'s track in the trace export."""
        if not self.enabled:
            return
        self._emit(
            "span", name,
            tv=float(tv_start), durv=float(tv_end) - float(tv_start),
            attrs=attrs,
        )

    def span_wall_at(self, name: str, t_start: float, t_end: float, **attrs):
        """Record a completed span on the **wall** clock from explicit
        :func:`perf_seconds` endpoints — per-request serving phases
        (queued / decode) whose boundaries interleave across requests, so
        no single context manager can bracket them."""
        if not self.enabled:
            return
        self._emit(
            "span", name,
            t=float(t_start) - self._epoch,
            dur=float(t_end) - float(t_start),
            attrs=attrs,
        )

    # -- metrics -----------------------------------------------------------

    def counter(self, name: str, inc: float = 1.0, **attrs) -> None:
        if not self.enabled:
            return
        self._emit("counter", name, value=float(inc), attrs=attrs)

    def gauge(self, name: str, value: float, **attrs) -> None:
        if not self.enabled:
            return
        if not self._sampled(attrs):
            return
        self._emit("gauge", name, value=float(value), attrs=attrs)

    def hist(self, name: str, value: float, **attrs) -> None:
        if not self.enabled:
            return
        if not self._sampled(attrs):
            return
        self._emit("hist", name, value=float(value), attrs=attrs)

    # -- progress / lifecycle ----------------------------------------------

    def progress(self, message: str, **attrs) -> None:
        """A human-facing progress line (rendered by ConsoleSink), which
        a disabled hub still hands to its console sinks."""
        sinks = None
        if not self.enabled:
            sinks = [s for s in self.sinks if isinstance(s, ConsoleSink)]
            if not sinks:
                return
        self._emit("progress", "progress", attrs={"message": message, **attrs},
                   sinks=sinks)

    def flush(self) -> None:
        for sink in self.sinks:
            sink.flush()

    def close(self) -> None:
        for sink in self.sinks:
            sink.close()


#: the no-op hub: disabled, sinkless — every call is an early return.
NULL_HUB = TelemetryHub(enabled=False)

#: process-default hub for engines constructed without one: progress
#: events render to stdout exactly like the print() calls they replaced.
_DEFAULT_HUB: Optional[TelemetryHub] = None

#: the process-global hub (kernel dispatch counters, trace-audit
#: republication — sites with no engine in reach); build() points it at
#: the experiment's hub for the duration of the run.
_GLOBAL_HUB: TelemetryHub = NULL_HUB


def default_hub() -> TelemetryHub:
    """The console-only hub engines fall back to when built without one."""
    global _DEFAULT_HUB
    if _DEFAULT_HUB is None:
        _DEFAULT_HUB = TelemetryHub(sinks=(ConsoleSink(),))
    return _DEFAULT_HUB


def get_hub() -> TelemetryHub:
    """The process-global hub (NULL_HUB until a build() installs one)."""
    return _GLOBAL_HUB


def set_hub(hub: TelemetryHub) -> TelemetryHub:
    """Install ``hub`` as the process-global hub; returns the previous."""
    global _GLOBAL_HUB
    prev = _GLOBAL_HUB
    _GLOBAL_HUB = hub
    return prev


def hub_from_spec(tspec, *, meta: Optional[dict] = None) -> TelemetryHub:
    """Build a hub from a ``TelemetrySpec``-shaped object (duck-typed:
    ``enabled`` / ``sinks`` / ``dir`` / ``sample_every``).

    A disabled spec gives a disabled hub with a console sink: progress
    lines keep printing exactly as before telemetry existed, nothing else
    builds an event, and spans reach only the profiler's trace.
    """
    if not tspec.enabled:
        return TelemetryHub(sinks=(ConsoleSink(),), enabled=False)
    return TelemetryHub(
        make_sinks(tspec.sinks, out_dir=tspec.dir),
        sample_every=tspec.sample_every,
        meta=meta,
    )
