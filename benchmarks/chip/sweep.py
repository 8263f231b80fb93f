"""Offered rate against tails and backlog, for a serving cell's mix.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --rates 4,8,12 --seconds 30

One server, warmed once; a window per rate, each drained before the
next.  The knee is the highest rate whose backlog does not grow through
the window; a cell's rate is set from it once, by hand, in its mix file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
for _p in (CHECKOUT / "src", CHECKOUT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import harness, run  # noqa: E402


def main(argv=None, *, bench: harness.Bench | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax

    run.enable_cache(jax)
    bench = bench or harness.Bench()
    cell = bench.cell(args.workload)
    kind = bench.kind(cell.traffic["kind"])
    rates = [float(r) for r in args.rates.split(",")]
    for row in kind.sweep(cell, args.seed, rates, args.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
