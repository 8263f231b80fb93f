"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig1_*       §4.1 heterogeneous least squares (variance correction)
  fig4_*       §4.1 homogeneous least squares (rank identification)
  fig3_*       communication/compute scaling + amortization point
  table1_*     measured vs analytic per-round communication
  fig5_*       CV proxy: accuracy vs client count, non-iid
  wire_*       wire codecs: measured bytes saved vs accuracy vs wall-clock
  kernel_*     low-rank chain vs dense matmul + Pallas interpret check
  sim_*        system simulator: time-to-target-loss, engines × stragglers
  roofline_*   dry-run roofline terms (requires results/dryrun/*.json)
  lint_*       repro-lint analyzer cost (dataflow tier runs on every PR)
  telemetry_*  telemetry hub overhead: disabled vs enabled vs jsonl sink
  serve_*      serving: factor-resident vs dense decode, continuous batching

Besides printing, every group persists its rows as a per-PR artifact
``<out-dir>/BENCH_<group>.json`` (schema: ``bench``, ``rows``,
``git_sha``, ``timestamp``) so perf claims stay comparable across PRs.

Run:  PYTHONPATH=src python -m benchmarks.run [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import contextlib
import datetime
import io
import json
import os
import subprocess
import sys


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, check=True,
        ).stdout.strip()
    except (OSError, subprocess.CalledProcessError):
        return "unknown"


class _Tee(io.TextIOBase):
    """Pass writes through to the real stdout while keeping a copy."""

    def __init__(self, real):
        self.real = real
        self.copy = io.StringIO()

    def write(self, s: str) -> int:
        self.real.write(s)
        self.copy.write(s)
        return len(s)

    def flush(self) -> None:
        self.real.flush()


@contextlib.contextmanager
def _record(group: str, out_dir: str, git_sha: str):
    """Capture the group's CSV rows and persist BENCH_<group>.json."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        yield
    rows = [ln for ln in tee.copy.getvalue().splitlines() if ln.strip()]
    artifact = {
        "bench": group,
        "rows": rows,
        "git_sha": git_sha,
        "timestamp": datetime.datetime.now(datetime.timezone.utc).isoformat(),
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{group}.json")
    with open(path, "w") as fh:
        json.dump(artifact, fh, indent=2)
        fh.write("\n")
    print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer rounds")
    ap.add_argument(
        "--smoke", action="store_true",
        help="minimal rounds — CI exercise of the benchmark drivers "
        "(implies --quick)",
    )
    ap.add_argument(
        "--only", type=str, default=None,
        help="comma-separated subset: lsq,costs,cv,wire,kernels,sim,"
        "ablation,roofline,lint,telemetry,serve",
    )
    ap.add_argument(
        "--out-dir", type=str, default="results",
        help="directory for the BENCH_<group>.json artifacts "
        "(default: results)",
    )
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    q = args.quick or args.smoke
    git_sha = _git_sha()

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    if want("lsq"):
        from benchmarks.bench_lsq import fig1_heterogeneous, fig4_homogeneous

        with _record("lsq", args.out_dir, git_sha):
            fig4_homogeneous(rounds=60 if q else 150)
            fig1_heterogeneous(rounds=80 if q else 200)
    if want("costs"):
        from benchmarks.bench_costs import fig3_scaling, table1_measured

        with _record("costs", args.out_dir, git_sha):
            fig3_scaling()
            table1_measured()
    if want("cv"):
        from benchmarks.bench_cv import fig5_partial, fig5_proxy

        with _record("cv", args.out_dir, git_sha):
            fig5_proxy(
                rounds=10 if q else 25, clients=(2, 4) if q else (2, 4, 8)
            )
            fig5_partial(
                rounds=10 if q else 25, C=8,
                cohorts=(8, 4) if q else (8, 4, 2),
            )
    if want("wire"):
        from benchmarks.bench_wire import wire_codecs

        with _record("wire", args.out_dir, git_sha):
            wire_codecs(rounds=3 if args.smoke else (10 if q else 25))
    if want("sim"):
        from benchmarks.bench_sim import sim_pareto

        with _record("sim", args.out_dir, git_sha):
            sim_pareto(rounds=10 if q else 25, smoke=args.smoke)
    if want("kernels"):
        from benchmarks.bench_kernels import chain_vs_dense

        with _record("kernels", args.out_dir, git_sha):
            chain_vs_dense()
    if want("ablation"):
        from benchmarks.bench_ablation import (
            participation_ablation,
            s_star_ablation,
            tau_ablation,
        )

        with _record("ablation", args.out_dir, git_sha):
            tau_ablation(rounds=50 if q else 120)
            s_star_ablation()
            participation_ablation(rounds=30 if q else 60)
    if want("roofline"):
        from benchmarks.bench_roofline import roofline_table

        with _record("roofline", args.out_dir, git_sha):
            roofline_table()
    if want("lint"):
        from benchmarks.bench_lint import lint_overhead

        with _record("lint", args.out_dir, git_sha):
            lint_overhead(repeats=1 if args.smoke else 3)
    if want("telemetry"):
        from benchmarks.bench_telemetry import telemetry_overhead

        with _record("telemetry", args.out_dir, git_sha):
            telemetry_overhead(rounds=3 if args.smoke else 6)
    if want("serve"):
        from benchmarks.bench_serve import serve_batching, serve_paths

        with _record("serve", args.out_dir, git_sha):
            serve_paths(smoke=args.smoke)
            serve_batching(smoke=args.smoke)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
