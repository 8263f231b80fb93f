"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (build, weights from the seed, compile or cache load, warm-up)
runs first and is timed as ``setup_s``; then the window is measured for ``--seconds``; then the
plain reference runs and decides ``correct``.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` traces the window (its first
``TRACE_SECONDS`` at most) with the profiler and reports its per-layer
metrics.  The last line of standard output is the result as one JSON object; the numbers compared, each with
its limit, are the last lines of standard error and the result's last
key.  Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[2]
for _p in (CHECKOUT / "src", CHECKOUT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import harness, trace  # noqa: E402

#: a traced run traces at most this much of its window: a second of
#: serving holds thousands of device operations
TRACE_SECONDS = 15.0


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_for(bench: harness.Bench, cell: str) -> int:
    for w in bench.manifest.get("workloads", []):
        if w["name"] == cell:
            return int(w["chips"])
    raise KeyError(f"cell {cell!r} is not in the benchmark's workloads")


def find_chips(jax_mod, chips: int):
    """The devices a cell runs on: ``chips`` TPU chips, or None where JAX
    finds fewer."""
    devs = jax_mod.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(
            f"this cell needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})",
            file=sys.stderr,
        )
        return None
    return devs[:chips]


def enable_cache(jax_mod) -> str:
    """JAX's persistent compile cache, inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names another; returns the directory."""
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # every program, however quick to compile or large, goes to the cache:
    # a second run of the cell then loads all of them
    jax_mod.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax_mod.config.update("jax_compilation_cache_max_size", -1)
    return cache


def main(argv=None, *, bench: harness.Bench | None = None,
         t_start: float = T_START) -> int:
    args = parse_args(argv)
    bench = bench or harness.Bench()
    cell = bench.cell(args.workload)

    import jax

    devs = find_chips(jax, chips_for(bench, args.workload))
    if devs is None:
        return 2
    cache = enable_cache(jax)
    counter = harness.CompileCounter().install()
    print(f"{cell.name}: seed {args.seed}, {len(devs)}x {devs[0].device_kind}, "
          f"compile cache {cache}", file=sys.stderr, flush=True)

    rec = harness.Record(cell=cell, seed=args.seed, device_kind=devs[0].device_kind,
                         peaks_file=bench.root / "peaks.json")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
        session = harness.Session(
            rec, seconds=seconds, trace=bool(args.trace), counter=counter,
            t_start=t_start, trace_dir=trace_dir,
        )
        bench.kind(cell.traffic["kind"]).run(session)
        if counter.count.get("window"):
            print(
                f"{counter.count['window']} compile(s) inside the window "
                f"({counter.seconds['window']:.3f} s): the set-up missed a shape",
                file=sys.stderr,
            )
            return 1
        device = rec.extra.pop("device")
        breakdown = None
        if args.trace:
            tr = trace.load(trace_dir)
            win = tr.window()
            rec.trace, rec.trace_window = tr, win
            rec.extra["per_layer"] = {
                m["name"]: bench.reader(m["name"]).read(rec) for m in cell.per_layer
            }
            device["busy_s"] = trace.mean_busy_s(tr, win)
            device["window_s"] = (win[1] - win[0]) / 1e9
            breakdown = {
                "device_ops": trace.top_ops(tr, win),
                "idle_gaps": trace.idle_gaps(tr, win),
            }
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(
        "compile seconds: "
        + ", ".join(f"{k} {v:.2f} ({counter.count[k]})" for k, v in counter.seconds.items()),
        file=sys.stderr,
    )
    harness.print_checks(rec)
    print(json.dumps(harness.result_line(rec, bool(args.trace), device, breakdown)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
