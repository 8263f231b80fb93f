"""CLI for spec files:  python -m repro.api {validate,describe,run,serve} ...

``validate`` parses + validates spec files and prints their content
hashes (the CI ``config-smoke`` job's first gate); ``describe`` renders a
built experiment without running it; ``run`` builds and trains, with the
same dotted ``--set section.key=value`` overrides the train CLI accepts;
``serve`` stands up the spec's ``[serve]`` section over seeded synthetic
prompts and prints throughput/latency stats.
"""
import argparse
import sys

from repro.api.spec import load_spec
from repro.utils.compile_cache import enable_compile_cache


def _load(path, overrides):
    spec = load_spec(path)
    if overrides:
        spec = spec.with_overrides(overrides)
    return spec


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m repro.api")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_val = sub.add_parser("validate", help="parse + validate spec files")
    p_val.add_argument("paths", nargs="+")

    p_desc = sub.add_parser("describe", help="build a spec and describe it")
    p_desc.add_argument("path")
    p_desc.add_argument("--set", dest="sets", action="append", default=[],
                        metavar="SECTION.KEY=VALUE")

    p_run = sub.add_parser("run", help="build a spec and train it")
    p_run.add_argument("path")
    p_run.add_argument("--set", dest="sets", action="append", default=[],
                       metavar="SECTION.KEY=VALUE")
    p_run.add_argument("--rounds", type=int, default=None,
                       help="override spec.rounds")
    p_run.add_argument("--log-every", type=int, default=None,
                       help="override spec.log_every")

    p_srv = sub.add_parser("serve", help="build a spec's serving stack and "
                           "drive synthetic requests through it")
    p_srv.add_argument("path")
    p_srv.add_argument("--set", dest="sets", action="append", default=[],
                       metavar="SECTION.KEY=VALUE")
    p_srv.add_argument("--requests", type=int, default=8,
                       help="number of synthetic prompts")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.cmd == "validate":
        ok = True
        for path in args.paths:
            try:
                spec = load_spec(path)
            except (ValueError, OSError) as e:
                print(f"{path}: INVALID — {e}")
                ok = False
            else:
                print(f"{path}: ok [spec {spec.spec_hash()}]")
        return 0 if ok else 1

    spec = _load(args.path, args.sets)
    if args.cmd == "serve":
        from repro.launch.serve import run_session

        return run_session(spec, num_requests=args.requests)

    from repro.api.experiment import build

    if args.cmd == "describe":
        print(build(spec).describe())
        return 0

    exp = build(spec)
    print(exp.describe())
    hist = exp.run(rounds=args.rounds, log_every=args.log_every)
    if not hist:
        print("done: no rounds run")
        return 0
    timing = (
        f"; virtual time {hist[-1].t_virtual:.1f}s [{spec.engine.kind}]"
        if exp.is_simulated
        else ""
    )
    print(
        f"done: loss {hist[0].loss_before:.4f} → {hist[-1].loss_before:.4f}; "
        f"total comm {exp.comm_total_bytes()/1e6:.1f} MB measured "
        f"[{spec.wire.codec}]{timing}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
