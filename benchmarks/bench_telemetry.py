"""Telemetry-hub overhead benchmark: the cost of observing a run.

The hub's contract is "a disabled hub builds no event and its span is
the profiler annotation alone (about a microsecond while no profiler
collects), an enabled hub costs microseconds per event" — this group
pins that, per hot-path operation and end-to-end through a real engine:

  telemetry_span_disabled   `with hub.span(...)` on a disabled hub
  telemetry_span_memory     same span on an enabled hub → MemorySink
  telemetry_counter_*       counter emission, disabled vs memory vs jsonl
  telemetry_gauge_sampled   off-cadence gauge (sample_every drops it)
  telemetry_run_off         3 sim rounds, telemetry disabled (baseline)
  telemetry_run_memory      same spec, memory sink
  telemetry_run_jsonl       same spec, jsonl sink (adds serialization+IO)

Rows follow the harness CSV: ``name,us_per_call,derived`` where derived
is events emitted (micro rows) or history length (run rows).  Engines
are built only through ``build(spec)``.
"""
from __future__ import annotations

import os
import tempfile
import time


def _time_op(fn, n: int):
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def _micro_rows() -> None:
    from repro.telemetry import JsonlSink, MemorySink, TelemetryHub

    N = 20_000
    off = TelemetryHub(enabled=False)

    def span_off():
        with off.span("s", round=0):
            pass

    print(f"telemetry_span_disabled,{_time_op(span_off, N):.3f},0")
    print(f"telemetry_counter_disabled,{_time_op(lambda: off.counter('c'), N):.3f},0")

    mem = TelemetryHub([MemorySink()])

    def span_mem():
        with mem.span("s", round=0):
            pass

    print(f"telemetry_span_memory,{_time_op(span_mem, N):.3f},{N}")
    print(f"telemetry_counter_memory,{_time_op(lambda: mem.counter('c'), N):.3f},{N}")

    sampled = TelemetryHub([MemorySink()], sample_every=1_000_000)
    print(
        "telemetry_gauge_sampled,"
        f"{_time_op(lambda: sampled.gauge('g', 1.0, round=1), N):.3f},0"
    )

    with tempfile.TemporaryDirectory() as d:
        js = TelemetryHub([JsonlSink(os.path.join(d, "events.jsonl"))])
        us = _time_op(lambda: js.counter("c", 1.0, round=0), N)
        js.close()
        print(f"telemetry_counter_jsonl,{us:.3f},{N}")


def _run_spec(rounds: int, telemetry_kw, out_dir=None):
    from repro.api import ExperimentSpec, build

    spec = ExperimentSpec.from_dict({
        "name": "bench-telemetry", "rounds": rounds, "log_every": 0,
        "model": {"kind": "mlp", "preset": None, "dim": 16, "classes": 4,
                  "hidden": 32, "r_max": 8, "kernels": "off"},
        "data": {"kind": "classification", "num_points": 512,
                 "holdout": 128, "batch": 16},
        "fed": {"clients": 4, "local_steps": 2, "eval_after": False},
        "engine": {"kind": "async", "buffer_size": 2},
        "sim": {"profile": "straggler:0.25,10"},
        "telemetry": telemetry_kw,
    })
    exp = build(spec)
    t0 = time.perf_counter()
    hist = exp.run()
    us = (time.perf_counter() - t0) * 1e6
    exp.hub.close()
    return us, len(hist)


def telemetry_overhead(rounds: int = 6) -> None:
    _micro_rows()
    # end-to-end: same spec and seed, three observation levels.  One
    # untimed warm-up run absorbs the first-build jit/tracing cost so the
    # three timed rows differ only in what they observe.
    _run_spec(rounds, {"enabled": False})
    us, n = _run_spec(rounds, {"enabled": False})
    print(f"telemetry_run_off,{us:.0f},{n}")
    us, n = _run_spec(rounds, {"enabled": True, "sinks": "memory"})
    print(f"telemetry_run_memory,{us:.0f},{n}")
    with tempfile.TemporaryDirectory() as d:
        us, n = _run_spec(
            rounds, {"enabled": True, "sinks": "jsonl", "dir": d}
        )
        print(f"telemetry_run_jsonl,{us:.0f},{n}")
