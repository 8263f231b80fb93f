"""Federated LM training driver — a thin CLI over :mod:`repro.api`.

The scenario lives in a declarative :class:`repro.api.ExperimentSpec`:
load one from a file, tweak it with dotted overrides, or drive it with the
legacy flags (every historical flag keeps working as an alias onto a spec
field).  Engine construction happens exclusively in
:func:`repro.api.build`.

    PYTHONPATH=src python -m repro.launch.train --preset llm-100m --rounds 300
    PYTHONPATH=src python -m repro.launch.train --preset none --arch qwen2-7b --smoke
    PYTHONPATH=src python -m repro.launch.train --config examples/configs/sync_baseline.toml \
        --set engine.kind=async --set sim.profile=straggler:0.25,10

``--preset`` and ``--arch`` are mutually exclusive (``--preset none``
selects the registry path); precedence is config file < legacy flags <
``--set`` overrides.

On the production mesh this module is launched once per host; the client
axis maps onto ("pod","data") exactly as in the dry-run (launch/dryrun.py
carries the sharding; this driver focuses on the algorithmic loop).
"""
from __future__ import annotations

import argparse
import dataclasses

from repro.api import ExperimentSpec, ParticipationSpec, build, load_spec
from repro.api.serialization import parse_override, set_dotted
from repro.api.tasks import PRESETS  # noqa: F401  (re-export: serve.py, tests)

#: legacy flag → spec field the alias writes (participation/preset/arch are
#: handled specially below)
FLAG_TO_FIELD = {
    "smoke": "model.smoke",
    "kernels": "model.kernels",
    "method": "fed.method",
    "correction": "fed.correction",
    "clients": "fed.clients",
    "local_steps": "fed.local_steps",
    "lr": "fed.lr",
    "tau": "fed.tau",
    "weighted": "fed.weighted",
    "wire_codec": "wire.codec",
    "edge_wire_codec": "wire.edge_codec",
    "engine": "engine.kind",
    "async_buffer": "engine.buffer_size",
    "staleness_power": "engine.staleness_power",
    "edges": "engine.edges",
    "edge_rounds": "engine.edge_rounds",
    "sim_profile": "sim.profile",
    "rounds": "rounds",
    "batch": "data.batch",
    "seq": "data.seq",
    "seed": "seed",
    "checkpoint_dir": "checkpoint.dir",
    "checkpoint_every": "checkpoint.every",
    "log_every": "log_every",
    "telemetry": "telemetry.enabled",
    "telemetry_dir": "telemetry.dir",
    "telemetry_sinks": "telemetry.sinks",
}


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        argument_default=argparse.SUPPRESS,  # only provided flags override
    )
    ap.add_argument("--config", type=str, default=None,
                    help="ExperimentSpec file (.toml or .json) to start from")
    ap.add_argument("--set", dest="sets", action="append", default=[],
                    metavar="SECTION.KEY=VALUE",
                    help="dotted spec override, e.g. --set engine.kind=async "
                    "(applied after config and legacy flags; repeatable)")
    ap.add_argument("--arch", type=str,
                    help="architecture registry id (mutually exclusive with "
                    "--preset; implies --preset none)")
    ap.add_argument("--preset", type=str,
                    choices=sorted(PRESETS) + ["none"],
                    help="named LM preset (default llm-tiny); 'none' selects "
                    "the --arch registry path")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--method", type=str,
                    choices=["fedlrt", "fedavg", "fedlin", "fedlrt_naive"])
    ap.add_argument("--correction", type=str,
                    choices=["none", "simplified", "full"])
    ap.add_argument("--clients", type=int)
    ap.add_argument("--participation", type=str,
                    help="per-round cohort policy: full | uniform:K | "
                    "round_robin:K | dropout:P")
    ap.add_argument("--weighted", action="store_true",
                    help="aggregate with client weights ∝ |X_c| (paper §2 "
                    "extension)")
    ap.add_argument("--kernels", choices=["auto", "interpret", "off"],
                    help="low-rank Pallas kernel dispatch: auto = fused "
                    "kernels on TPU (jnp reference elsewhere), interpret = "
                    "force the Pallas interpreter (CPU validation, slow), "
                    "off = plain jnp chain")
    ap.add_argument("--wire-codec", type=str,
                    help="on-the-wire codec for round payloads: identity | "
                    "downcast[:dtype] | int8_affine | topk_rank (see "
                    "repro.fed.wire); comm totals are measured through it")
    ap.add_argument("--engine", choices=["sync", "async", "hier"],
                    help="aggregation engine: sync (one barrier per round), "
                    "async (FedBuff-style buffered, --async-buffer arrivals "
                    "per aggregate), hier (two-tier edge→cloud; "
                    "--edges/--edge-rounds)")
    ap.add_argument("--sim-profile", type=str,
                    help="client system-profile fleet for virtual-clock "
                    "pricing: uniform | straggler[:FRAC[,SLOWDOWN]] | "
                    "lognormal[:SIGMA] (optionally prefixed dropout:P,). "
                    "Implied 'uniform' for the async/hier engines; omit "
                    "entirely for the plain sync engine")
    ap.add_argument("--async-buffer", type=int,
                    help="async engine: aggregate every K arrivals "
                    "(default: #clients)")
    ap.add_argument("--staleness-power", type=float,
                    help="async engine: staleness discount (1+s)^-p on "
                    "stale updates")
    ap.add_argument("--edges", type=int,
                    help="hier engine: number of edge servers")
    ap.add_argument("--edge-rounds", type=int,
                    help="hier engine: local rounds per cloud round")
    ap.add_argument("--edge-wire-codec", type=str,
                    help="hier engine: codec for the edge→cloud hop "
                    "(default: --wire-codec)")
    ap.add_argument("--rounds", type=int)
    ap.add_argument("--local-steps", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--lr", type=float)
    ap.add_argument("--tau", type=float)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--checkpoint-dir", type=str)
    ap.add_argument("--checkpoint-every", type=int,
                    help="checkpoint cadence in rounds (needs "
                    "--checkpoint-dir; default 20)")
    ap.add_argument("--log-every", type=int)
    ap.add_argument("--telemetry", action="store_true",
                    help="structured telemetry: round/phase spans, metric "
                    "streams, JSONL event log + Perfetto trace (see "
                    "repro.telemetry; on ≡ off bit-for-bit)")
    ap.add_argument("--telemetry-dir", type=str,
                    help="output directory for the jsonl/perfetto sinks "
                    "(events.jsonl, trace.json)")
    ap.add_argument("--telemetry-sinks", type=str,
                    help="comma list over console,memory,jsonl,perfetto "
                    "(default console)")
    return ap


def spec_from_argv(argv=None) -> ExperimentSpec:
    """Resolve CLI arguments into a validated :class:`ExperimentSpec`.

    Precedence: ``--config`` file < legacy flag aliases < ``--set``.
    """
    ap = _parser()
    args = vars(ap.parse_args(argv))
    sets = args.pop("sets")
    config = args.pop("config")
    spec = load_spec(config) if config else ExperimentSpec()

    # model selection: --preset and --arch are mutually exclusive ("none"
    # is the explicit opt-out; previously --arch silently clobbered the
    # preset default and `choices=list(PRESETS) + [None]` was untypable)
    preset = args.pop("preset", None)
    arch = args.pop("arch", None)
    if preset is not None and preset != "none" and arch is not None:
        ap.error("--preset and --arch are mutually exclusive "
                 "(pass --preset none to use --arch)")
    assignments = {}
    if arch is not None:
        assignments.update({"model.preset": None, "model.arch": arch})
    elif preset == "none":
        assignments["model.preset"] = None
    elif preset is not None:
        assignments.update({"model.preset": preset, "model.arch": None})

    if "participation" in args:
        p = ParticipationSpec.from_string(args.pop("participation"))
        for f in dataclasses.fields(p):
            assignments[f"participation.{f.name}"] = getattr(p, f.name)

    # the variance correction only parameterizes FeDLRT: dense methods get
    # correction='none' implicitly (the legacy CLI's silent coercion), and
    # an *explicit* contradictory --correction is a hard error at spec time
    method = args.get("method")
    if method is not None and not method.startswith("fedlrt"):
        args.setdefault("correction", "none")
    assignments.update({FLAG_TO_FIELD[k]: v for k, v in args.items()})

    # one mutation pass over the plain dict, one validation at the end —
    # flag/override combinations never trip on transient intermediate states
    data = spec.to_dict()
    for path, value in assignments.items():
        set_dotted(ExperimentSpec, data, path, value, parse_str=False)
    for item in sets:
        path, raw = parse_override(item)
        set_dotted(ExperimentSpec, data, path, raw, parse_str=True)
    return ExperimentSpec.from_dict(data)


def main(argv=None):
    from repro.utils.compile_cache import enable_compile_cache

    spec = spec_from_argv(argv)
    enable_compile_cache()
    exp = build(spec)
    print(f"{exp.task.description} clients={spec.fed.clients} "
          f"[spec {spec.spec_hash()}]")
    hist = exp.run()
    import numpy as np

    mean_cohort = np.mean([r.cohort_size for r in hist])
    # condition on the *scenario*, not `t_virtual`'s truthiness — a
    # legitimately-zero clock reading (sync engine + profile at round 0)
    # must still print the engine timing
    timing = (
        f"; virtual time {hist[-1].t_virtual:.1f}s [{spec.engine.kind}]"
        if exp.is_simulated
        else ""
    )
    eng = exp.engine
    analytic = (
        f" vs {eng.comm_total_bytes_analytic()/1e6:.1f} MB analytic"
        if hasattr(eng, "comm_total_bytes_analytic") else ""
    )
    print(
        f"done: loss {hist[0].loss_before:.4f} → {hist[-1].loss_before:.4f}; "
        f"total comm {eng.comm_total_bytes()/1e6:.1f} MB measured "
        f"[{spec.wire.codec}]{analytic} (mean cohort {mean_cohort:.1f}/"
        f"{spec.fed.clients}){timing}"
    )
    return hist


if __name__ == "__main__":
    main()
