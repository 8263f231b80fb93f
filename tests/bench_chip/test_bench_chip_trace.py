"""The trace reduction, on hand-made events and on a trace recorded on the CPU."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_chip_tiny as tiny  # noqa: E402
from benchmarks.chip import trace  # noqa: E402


def _trace(ops, spans):
    return trace.Trace(device_ops={"/device:TPU:0": ops}, host_spans=spans)


def test_union_merges_overlaps_and_touching_intervals():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 15, 5), ("d", 30, 10), ("e", 32, 2)]
    assert trace.union(ops) == [(0, 20), (30, 40)]


def test_idle_share_is_one_minus_busy_over_the_window():
    ops = [("a", 0, 10), ("b", 5, 10), ("c", 60, 20), ("d", 90, 50)]
    tr = _trace(ops, [])
    win = (0.0, 100.0)
    # busy: [0, 15) and [60, 80) and [90, 100) clipped → 45 of 100
    assert trace.busy_ns(ops, win) == 45
    assert trace.idle_share(tr, win) == pytest.approx(0.55)
    assert trace.mean_busy_s(tr, win) == pytest.approx(45e-9)


def test_idle_share_averages_over_devices():
    tr = trace.Trace(
        device_ops={"/device:TPU:0": [("a", 0, 100)], "/device:TPU:1": [("a", 0, 50)]},
        host_spans=[],
    )
    assert trace.idle_share(tr, (0.0, 100.0)) == pytest.approx(0.25)


def test_idle_gaps_go_to_the_innermost_covering_host_span():
    ops = [("a", 0, 10), ("b", 30, 10), ("c", 70, 30)]
    spans = [
        ("bench.window", 0, 100),
        ("bench.step", 0, 50),
        ("bench.submit", 10, 20),   # covers the whole gap [10, 30)
        ("bench.step", 50, 50),
    ]
    gaps = dict(trace.idle_gaps(_trace(ops, spans), (0.0, 100.0)))
    assert gaps["bench.submit"] == pytest.approx(20e-9)
    assert gaps["bench.step"] == pytest.approx(30e-9)  # [40, 70)
    assert "bench.window" not in gaps


def test_top_ops_merges_numbered_copies_and_skips_control_flow():
    ops = [("fusion.1", 0, 5), ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.1)", 5, 5),
           ("dot.7", 10, 3), ("%while.3 = (s32[]) while((s32[]) %t), body=%b", 0, 13),
           ("%cond.4 = (s32[]) conditional(s32[] %i, ...)", 0, 13)]
    assert trace.top_ops(_trace(ops, []), (0.0, 100.0)) == [
        ["fusion", pytest.approx(10e-9)], ["dot", pytest.approx(3e-9)]
    ]


def test_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()

    assert trace.load(str(tmp_path)).host_spans
    tr = tiny.load_cpu_trace(str(tmp_path))
    lo, hi = tr.window()
    assert hi > lo
    assert [n for n, _, _ in tr.host_spans].count("bench.step") == 3
    ops = next(iter(tr.device_ops.values()))
    assert ops, "no XLA operation found in the trace"
    busy = trace.busy_ns(ops, (lo, hi))
    assert 0 < busy <= hi - lo
    assert 0.0 <= trace.idle_share(tr, (lo, hi)) < 1.0
    names = [n for n, _ in trace.top_ops(tr, (lo, hi))]
    assert any("dot" in n for n in names), names
    # every gap lies in the window, so the gaps and the busy time add up
    gaps = sum(s for _, s in trace.idle_gaps(tr, (lo, hi)))
    assert gaps * 1e9 + busy == pytest.approx(hi - lo, rel=1e-6)
