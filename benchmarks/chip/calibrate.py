"""Readings that a cell's correctness limits are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds 15] [--out calib.jsonl]

In one process, for every seed, the program's reading of every number
the comparison can make against the plain reference; on the control
seeds also the control's (the reference in the precision below the
configuration's, in the program's place) and each fault's that the cell
can have.  The traffic kind says how (``calibrate`` in
``traffic/<kind>.py``).  One JSON line per seed.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    run.enable_cache(jax)
    bench = bench or harness.Bench()
    cell = bench.cell(args.workload)
    kind = bench.kind(cell.traffic["kind"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    try:
        for line in kind.calibrate(cell, seeds, control_seeds, seconds=args.seconds):
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
