"""The structured telemetry subsystem (repro.telemetry).

Pins, in order:

- the load-bearing invariant: a telemetry-enabled run is **bit-for-bit**
  identical to a disabled one — same params, same round history (modulo
  ``RoundResult.seconds``, which is host wall time by definition);
- the event schema: everything a real run emits validates, the JSONL
  sink round-trips losslessly against an in-memory sink, and
  ``validate_event`` rejects malformed events;
- the Perfetto exporter: a 3-round async straggler run exports valid
  ``trace_event`` JSON with per-client tracks on both the wall and the
  virtual clock, monotone timestamps per track;
- hub mechanics: a disabled hub emits nothing and its spans are the
  profiler annotation alone; an enabled hub's span is a sink event and an
  annotation; ``sample_every`` drops only gauge/hist events off-cadence.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax

from repro.api import (
    DataSpec,
    EngineSpec,
    ExperimentSpec,
    FedSpec,
    ModelSpec,
    SimSpec,
    TelemetrySpec,
    build,
)
from repro.telemetry import (
    NULL_HUB,
    MemorySink,
    TelemetryHub,
    events_to_trace,
    validate_event,
    validate_jsonl,
)
from repro.telemetry.perfetto import SERVER_TID, VIRTUAL_PID, WALL_PID

# ---------------------------------------------------------------------------
# fixtures: a tiny async straggler scenario
# ---------------------------------------------------------------------------


def async_spec(**telemetry) -> ExperimentSpec:
    return ExperimentSpec(
        name="telemetry-pin",
        rounds=3,
        log_every=0,
        model=ModelSpec(kind="mlp", dim=16, classes=4, hidden=32, r_max=8,
                        kernels="off"),
        data=DataSpec(kind="classification", batch=16, num_points=512,
                      holdout=128),
        fed=FedSpec(method="fedlrt", correction="simplified", clients=4,
                    local_steps=2, lr=5e-2, tau=0.03, eval_after=False),
        engine=EngineSpec(kind="async", buffer_size=2),
        sim=SimSpec(profile="straggler:0.25,10"),
        telemetry=TelemetrySpec(**telemetry) if telemetry else TelemetrySpec(),
    )


def run_spec(spec):
    exp = build(spec)
    hist = exp.run()
    exp.hub.close()
    return exp, hist


# ---------------------------------------------------------------------------
# telemetry on ≡ off, bit-for-bit
# ---------------------------------------------------------------------------


def test_enabled_matches_disabled_bit_for_bit():
    exp_off, hist_off = run_spec(async_spec())
    exp_on, hist_on = run_spec(
        async_spec(enabled=True, sinks="memory")
    )
    # params: exact equality, leaf by leaf
    la, lb = jax.tree.leaves(exp_off.params), jax.tree.leaves(exp_on.params)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # history: every field except the wall-clock `seconds`
    assert len(hist_off) == len(hist_on)
    for ra, rb in zip(hist_off, hist_on):
        for f in dataclasses.fields(ra):
            if f.name == "seconds":
                continue
            va, vb = getattr(ra, f.name), getattr(rb, f.name)
            if f.name == "ranks":
                assert sorted(va) == sorted(vb)
                for k in va:
                    np.testing.assert_array_equal(va[k], vb[k])
            elif isinstance(va, np.ndarray) or isinstance(vb, np.ndarray):
                np.testing.assert_array_equal(va, vb)
            else:
                assert va == vb, (f.name, va, vb)
    # and the enabled run actually observed something
    [sink] = [s for s in exp_on.hub.sinks if isinstance(s, MemorySink)]
    assert len(sink.events) > 0


# ---------------------------------------------------------------------------
# event schema + JSONL round-trip
# ---------------------------------------------------------------------------


def test_jsonl_sink_schema_roundtrip(tmp_path):
    out = tmp_path / "telemetry"
    spec = async_spec(enabled=True, sinks="memory,jsonl", dir=str(out))
    exp, _ = run_spec(spec)
    path = out / "events.jsonl"
    assert path.exists()
    assert validate_jsonl(path) == []
    [mem] = [s for s in exp.hub.sinks if isinstance(s, MemorySink)]
    with open(path) as fh:
        from_disk = [json.loads(line) for line in fh]
    # JSONL round-trips the in-memory stream losslessly (json floats are
    # repr-exact), and every event validates individually
    assert from_disk == mem.events
    for ev in mem.events:
        assert validate_event(ev) == []
    # the hot seams all showed up
    names = {(e["kind"], e["name"]) for e in mem.events}
    assert ("meta", "hub_start") in names
    assert ("span", "client_round") in names
    assert ("span", "aggregate") in names
    assert ("counter", "sim.events_popped") in names
    assert ("gauge", "rank.effective_mean") in names
    assert ("gauge", "staleness_mean") in names


def test_validate_event_rejects_malformed():
    ok = {
        "kind": "gauge", "name": "x", "t": 0.0, "dur": None, "tv": None,
        "durv": None, "value": 1.0, "attrs": {"round": 0}, "seq": 0,
    }
    assert validate_event(ok) == []
    assert validate_event("nope") != []
    assert validate_event({**ok, "kind": "bogus"}) != []
    assert validate_event({**ok, "value": None}) != []  # gauge needs a value
    assert validate_event({**ok, "attrs": {"x": [1, 2]}}) != []
    assert validate_event({**ok, "extra": 1}) != []
    missing = dict(ok)
    del missing["seq"]
    assert validate_event(missing) != []


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def test_perfetto_export_async_straggler(tmp_path):
    out = tmp_path / "telemetry"
    spec = async_spec(
        enabled=True, sinks="memory,perfetto", dir=str(out)
    )
    exp, _ = run_spec(spec)
    trace_path = out / "trace.json"
    assert trace_path.exists()
    with open(trace_path) as fh:
        trace = json.load(fh)
    evs = trace["traceEvents"]
    assert evs, "empty trace"
    for ev in evs:
        assert ev["ph"] in ("X", "C", "M")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    # per-client tracks on the virtual clock (tid = client + 1); the 10×
    # straggler's first round may still be in flight after 3 aggregates,
    # so expect the three fast clients at least
    client_tids = {
        ev["tid"] for ev in evs
        if ev["ph"] == "X" and ev["pid"] == VIRTUAL_PID
        and ev["tid"] != SERVER_TID
    }
    assert len(client_tids) >= 3
    assert client_tids <= {c + 1 for c in range(4)}
    # ... and the server aggregate track exists on the virtual clock too
    assert any(
        ev["ph"] == "X" and ev["pid"] == VIRTUAL_PID
        and ev["tid"] == SERVER_TID
        for ev in evs
    )
    # monotone timestamps per (pid, tid) track, in emission order
    last = {}
    for ev in evs:
        if ev["ph"] != "X":
            continue
        key = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last.get(key, float("-inf")), (
            f"track {key} went backwards at {ev['name']!r}"
        )
        last[key] = ev["ts"]
    # track metadata names both clock processes
    meta_names = {
        ev["args"]["name"] for ev in evs if ev["ph"] == "M"
        and ev["name"] == "process_name"
    }
    assert meta_names == {"wall clock", "virtual clock"}
    # the in-memory stream exports to the identical trace
    [mem] = [s for s in exp.hub.sinks if isinstance(s, MemorySink)]
    assert events_to_trace(mem.events) == trace
    assert WALL_PID in {ev["pid"] for ev in evs}


# ---------------------------------------------------------------------------
# hub mechanics
# ---------------------------------------------------------------------------


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; the host events it recorded, as
    {name: [stats dict]}."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_disabled_hub_is_noop(tmp_path):
    sink = MemorySink()
    hub = TelemetryHub([sink], enabled=False)

    def observe():
        with hub.span("x", round=0, rows=7):
            pass
        hub.span_at("y", 0.0, 1.0)
        hub.counter("c")
        hub.gauge("g", 1.0)
        hub.hist("h", 1.0)
        hub.progress("hello")

    host = _profiled(tmp_path, observe)
    assert sink.events == []
    # the span is the profiler's annotation alone, its attrs as stats
    assert host["x"] == [{"round": 0, "rows": 7}]
    assert not {"y", "c", "g", "h"} & set(host)
    assert NULL_HUB.enabled is False


def test_enabled_hub_span_is_a_sink_event_and_an_annotation(tmp_path):
    sink = MemorySink()
    hub = TelemetryHub([sink])

    def observe():
        with hub.span("serve.emit", step=3) as sp:
            sp.set_metadata(done=2)

    host = _profiled(tmp_path, observe)
    [ev] = [e for e in sink.events if e["kind"] == "span"]
    assert ev["name"] == "serve.emit" and ev["dur"] >= 0
    assert ev["attrs"] == {"step": 3, "done": 2}
    assert host["serve.emit"] == [{"step": 3, "done": 2}]


def test_a_disabled_spec_keeps_progress_lines_only(capsys):
    from repro.telemetry import hub_from_spec

    hub = hub_from_spec(TelemetrySpec(enabled=False))
    assert hub.enabled is False
    hub.counter("c")
    hub.gauge("g", 1.0)
    with hub.span("s") as sp:
        sp.set_metadata(done=1)
    hub.progress("round 3 done")
    assert capsys.readouterr().out == "round 3 done\n"


def test_sample_every_drops_offcadence_gauges():
    sink = MemorySink()
    hub = TelemetryHub([sink], sample_every=2)
    for r in range(4):
        hub.gauge("g", float(r), round=r)
        hub.hist("h", float(r), round=r)
        hub.counter("c", 1.0, round=r)  # counters are never sampled
        with hub.span("s", round=r):  # spans are never sampled
            pass
    kinds = [(e["kind"], e["attrs"].get("round")) for e in sink.events
             if e["kind"] != "meta"]
    gauges = [r for k, r in kinds if k == "gauge"]
    hists = [r for k, r in kinds if k == "hist"]
    counters = [r for k, r in kinds if k == "counter"]
    spans = [r for k, r in kinds if k == "span"]
    assert gauges == [0, 2] and hists == [0, 2]
    assert counters == [0, 1, 2, 3] and spans == [0, 1, 2, 3]


def test_console_sink_renders_progress_only(capsys):
    from repro.telemetry import ConsoleSink

    hub = TelemetryHub([ConsoleSink()])
    hub.gauge("g", 1.0)
    hub.progress("round 3 done")
    out = capsys.readouterr().out
    assert "round 3 done" in out
    assert "g" not in out.replace("round 3 done", "")


def test_virtual_clock_attaches():
    from repro.fed.sim.clock import VirtualClock

    sink = MemorySink()
    hub = TelemetryHub([sink])
    clock = VirtualClock()
    hub.attach_clock(clock)
    clock.advance_to(2.5)
    hub.counter("c")
    ev = [e for e in sink.events if e["kind"] == "counter"][-1]
    assert ev["tv"] == 2.5
    hub.span_at("s", 1.0, 2.0)
    sp = [e for e in sink.events if e["kind"] == "span"][-1]
    assert sp["tv"] == 1.0 and sp["durv"] == 1.0


def test_trace_audit_publishes_counters():
    from repro.analysis.trace_audit import TraceAudit

    audit = TraceAudit()
    audit.record(("eng.py", 10, "step"))
    audit.record(("eng.py", 10, "step"))
    audit.record(("eng.py", 40, "phase"))
    sink = MemorySink()
    audit.publish(TelemetryHub([sink]))
    evs = [e for e in sink.events if e["name"] == "jit.traces"]
    assert [(e["value"], e["attrs"]["site"]) for e in evs] == [
        (2.0, "eng.py:10"), (1.0, "eng.py:40"),
    ]


def test_telemetry_spec_validates():
    with pytest.raises(ValueError, match="sample_every"):
        TelemetrySpec(sample_every=0)
    with pytest.raises(ValueError, match="unknown telemetry sink"):
        TelemetrySpec(sinks="console,bogus")
    with pytest.raises(ValueError, match="telemetry.dir"):
        TelemetrySpec(enabled=True, sinks="jsonl")
    # disabled spec may name file sinks without a dir (nothing is opened)
    TelemetrySpec(enabled=False, sinks="jsonl")


def test_spec_toml_roundtrip_with_telemetry(tmp_path):
    spec = async_spec(enabled=True, sinks="memory", sample_every=3)
    assert ExperimentSpec.from_toml(spec.to_toml()) == spec
    # old configs without a [telemetry] table stay valid (defaults)
    plain = async_spec()
    d = plain.to_dict()
    d.pop("telemetry")
    assert ExperimentSpec.from_dict(d) == plain
