"""The program's spans in a traced run, and the per-layer metrics of the
serving step that read them: on hand-made spans, on a trace recorded on
the CPU, and in a traced run of the tiny cell under a manifest that lists
the four metrics."""
import dataclasses
import json
import sys
from pathlib import Path
from unittest import mock

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_chip_tiny as tiny  # noqa: E402
from benchmarks.chip import harness, spans, trace  # noqa: E402

#: the manifest entries of the four metrics, as the chat cell lists them
PROGRAM_METRICS = [
    {"name": name, "unit": unit, "better": "lower", "source": "program_span",
     "layer": layer, "moves": "itl_p95_ms", "workloads": ["qwen2-7b.serve.chat"]}
    for name, unit, layer in [
        ("serve_admit_ms", "ms", "serve engine"),
        ("serve_decode_ms", "ms", "serve engine"),
        ("serve_host_ms", "ms", "scheduler"),
        ("serve_prefill_pad_share", "%", "serve engine"),
    ]
]


def _step(t0, admits=(), decode=10.0, sample=20.0, emit=5.0, gap=1.0):
    """One hand-made ``serve.step`` and its children from ``t0`` (ns):
    each admission as (prompt_len, bucket, its own sample), then the decode
    step, its sample and the emit, ``gap`` apart."""
    out, t = [], t0 + gap
    for i, (plen, bucket, s1) in enumerate(admits):
        start = t
        t += gap
        out.append(("serve.prefill", t, 3.0, {"bucket": bucket}))
        t += 3.0 + gap
        out.append(("serve.sample", t, s1, {"rows": 1}))
        t += s1 + gap
        out.append(("serve.insert", t, 2.0, {}))
        t += 2.0 + gap
        out.insert(-3, ("serve.admit", start, t - start,
                        {"rid": i, "prompt_len": plen, "bucket": bucket}))
        t += gap
    out.append(("serve.decode_step", t, decode, {}))
    t += decode + gap
    out.append(("serve.sample", t, sample, {"rows": 4}))
    t += sample + gap
    out.append(("serve.emit", t, emit, {"done": 0}))
    t += emit + gap
    return [("serve.step", t0, t - t0, {"step": 0, "active": 1, "queued": 0})] + out, t


def test_children_follow_the_nesting_of_hand_made_spans():
    step, _ = _step(0.0, admits=[(5, 16, 7.0)])
    kids = spans.children(step)
    assert [k[0] for k in kids[-1]] == ["serve.step"]
    assert [k[0] for k in kids[0]] == [
        "serve.admit", "serve.decode_step", "serve.sample", "serve.emit"]
    assert [k[0] for k in kids[1]] == ["serve.prefill", "serve.sample", "serve.insert"]


def test_the_metrics_of_hand_made_steps():
    a, t = _step(0.0, admits=[(5, 16, 7.0), (20, 32, 9.0)])
    b, t = _step(t, decode=12.0, sample=30.0)
    c, _ = _step(t, decode=11.0, sample=25.0)
    ev = spans.within(a + b + c, (0.0, 1e9))
    assert spans.decode_ms(ev) == pytest.approx(36e-6)  # median of 30, 42, 36 ns
    # a step less every sample inside it: a's admissions' samples too
    hosts = [s[2] - sum(x[2] for x in blk if x[0] == "serve.sample")
             for blk in (a, b, c) for s in blk[:1]]
    assert spans.host_ms(ev) == pytest.approx(sorted(hosts)[1] / 1e6)
    admits = [x[2] for x in a if x[0] == "serve.admit"]
    assert spans.admit_ms(ev) == pytest.approx(sum(admits) / 2 / 1e6)
    assert spans.prefill_pad_share(ev) == 100.0 * (48 - 25) / 48
    slow = spans.slowest_steps(ev, n=2)
    assert [s[0] for s in slow] == sorted([round(s[0][2] / 1e6, 3) for s in (a, b, c)],
                                          reverse=True)[:2]


def test_no_program_span_gives_no_metric():
    assert spans.admit_ms([]) is None and spans.decode_ms([]) is None
    assert spans.host_ms([]) is None and spans.prefill_pad_share([]) is None


def test_a_window_keeps_only_the_spans_wholly_inside_it():
    ev = [("serve.step", 0.0, 10.0, {}), ("serve.step", 5.0, 10.0, {}),
          ("serve.step", 20.0, 10.0, {})]
    assert [s[1] for s in spans.within(ev, (0.0, 25.0))] == [0.0, 5.0]


def test_idle_gaps_go_to_the_innermost_program_span():
    ops = [("a", 0, 10), ("b", 30, 20), ("c", 60, 20), ("d", 95, 5)]
    host = [("bench.window", 0, 100), ("bench.step", 0, 100)]
    prog = [("serve.step", 1, 98), ("serve.admit", 5, 35),
            ("serve.sample", 8, 24), ("serve.emit", 48, 14)]
    tr = trace.Trace(device_ops={"/device:TPU:0": ops}, host_spans=host)
    gaps = dict(trace.idle_gaps(tr, (0.0, 100.0), spans=host + prog))
    assert gaps == {"serve.sample": pytest.approx(20e-9),  # [10, 30)
                    "serve.emit": pytest.approx(10e-9),    # [50, 60)
                    "serve.step": pytest.approx(15e-9)}    # [80, 95)
    # the idle time is the same whichever spans it is put down to
    assert dict(trace.idle_gaps(tr, (0.0, 100.0))) == {"bench.step": pytest.approx(45e-9)}


def test_idle_time_is_split_among_the_innermost_spans_it_crosses():
    # one gap [40, 70) runs from a step's emit through the benchmark's
    # loop into the next step's decode dispatch
    ops = [("a", 0, 40), ("b", 70, 30)]
    host = [("bench.window", 0, 100), ("bench.step", 0, 50), ("bench.step", 55, 45)]
    prog = [("serve.step", 1, 48), ("serve.emit", 35, 12),
            ("serve.step", 60, 39), ("serve.decode_step", 62, 10)]
    tr = trace.Trace(device_ops={"/device:TPU:0": ops}, host_spans=host)
    inner = dict(spans.idle_by_innermost(tr, (0.0, 100.0), host + prog))
    assert inner == {"serve.emit": pytest.approx(7e-9),       # [40, 47)
                     "serve.step": pytest.approx(4e-9),       # [47, 49), [60, 62)
                     "bench.step": pytest.approx(6e-9),       # [49, 50), [55, 60)
                     "none": pytest.approx(5e-9),             # [50, 55)
                     "serve.decode_step": pytest.approx(8e-9)}  # [62, 70)
    # trace.idle_gaps gives the whole gap to the one span that covers
    # most of it: the second step of the benchmark's loop
    assert dict(trace.idle_gaps(tr, (0.0, 100.0), spans=host + prog)) == {
        "bench.step": pytest.approx(30e-9)}


def test_program_spans_are_read_from_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import numpy as np

    from repro.api import ExperimentSpec, ModelSpec, ServeSpec, serve
    from repro.serve import Request

    sess = serve(ExperimentSpec(
        model=ModelSpec(kind="lm", preset="llm-tiny", smoke=True),
        serve=ServeSpec(max_batch=2, max_prompt=16, prompt_bucket=8, max_new_tokens=3),
    ))
    reqs = [Request(rid=i, tokens=np.arange(1, 4 + 3 * i, dtype=np.int32)) for i in range(3)]
    sess.run(reqs)  # compiles
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        sess.run([dataclasses.replace(r, rid=r.rid + 10) for r in reqs])
    jax.profiler.stop_trace()
    [path] = Path(tmp_path).rglob("*.xplane.pb")
    ev, window = spans.load(str(path))
    ev = spans.within(ev, window)
    admits = [a for n, _, _, a in ev if n == "serve.admit"]
    assert [(a["rid"], a["prompt_len"], a["bucket"]) for a in admits] == [
        (10, 3, 8), (11, 6, 8), (12, 9, 16)]
    assert {n for n, _, _, _ in ev} == {
        "serve.step", "serve.admit", "serve.prefill", "serve.sample",
        "serve.insert", "serve.decode_step", "serve.emit"}
    assert spans.prefill_pad_share(ev) == 100.0 * (32 - 18) / 32
    assert all(v > 0 for v in (spans.admit_ms(ev), spans.decode_ms(ev), spans.host_ms(ev)))
    # trace.load keeps the benchmark's spans only: the device numbers do
    # not see the program's
    tr = tiny.load_cpu_trace(str(tmp_path))
    assert not [n for n, _, _ in tr.host_spans if n.startswith(spans.PREFIX)]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root, manifest = tiny.make_tree(tmp_path_factory.mktemp("bench"))
    data = json.loads(manifest.read_text())
    data["per_layer"] += [dict(m, workloads=m["workloads"] + [tiny.SERVE_CELL])
                          for m in PROGRAM_METRICS]
    manifest.write_text(json.dumps(data))
    return root, manifest


def test_a_traced_run_reports_the_program_span_metrics(tree):
    seen = {}
    real = harness.result_line

    def keep(rec, *a, **k):
        seen["rec"] = rec
        return real(rec, *a, **k)

    with mock.patch.object(harness, "result_line", keep):
        rc, res, err = tiny.run_cell(*tree, seed=2**31 + 11, trace=1)
    assert rc == 0 and res["correct"] is True, err[-3000:]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == {"device_idle_share.serve", "serve_decode_mfu"} | {
        x["name"] for x in PROGRAM_METRICS}
    for x in PROGRAM_METRICS:
        assert 0 < m[x["name"]] < float("inf"), x["name"]
        assert res["metrics"][x["name"]]["unit"] == x["unit"]
    assert "slowest serve.step" in err and "idle time by innermost span" in err

    rec = seen["rec"]
    # the pad share counts the prompts that the window's steps admitted
    bucket = tiny.SERVE_MIX["prompt_bucket"]
    lens = [p for t, _, admitted, _, _ in rec.extra["serve_log"].steps
            if t <= rec.window_s for p in admitted]
    total = sum(-(-p // bucket) * bucket for p in lens)
    assert m["serve_prefill_pad_share"] == 100.0 * (total - sum(lens)) / total

    # the accepted metrics read the same with the program's spans among
    # the trace's host spans
    bench = harness.Bench(*tree)
    before = {n: bench.reader(n).read(rec) for n in ("device_idle_share.serve", "serve_decode_mfu")}
    prog = [(n, s, d) for n, s, d, _ in spans.of_record(rec)]
    assert prog
    rec.trace = dataclasses.replace(rec.trace, host_spans=rec.trace.host_spans + prog)
    after = {n: bench.reader(n).read(rec) for n in before}
    assert after == before == {n: m[n] for n in before}
