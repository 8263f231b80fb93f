import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# --------------------------------------------------------------------------
# Multi-pod dry-run (deliverable e): lower + compile every
# (architecture × input shape) on the production meshes, print
# memory_analysis / cost_analysis, and persist the roofline terms.
#
# The two environment lines above MUST run before any other import — jax
# locks the device count and the platform at first initialization.  This
# is a compile-only tool on fake host devices: pinning the CPU platform
# keeps it (and every --all child, which inherits the variable) off an
# attached accelerator, which belongs to one process at a time.  Do not
# move them; do not set these anywhere global (tests/benches must see the
# single real CPU device).
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
#   python -m repro.launch.dryrun --arch qwen2-7b --shape decode_32k --multi-pod
#   python -m repro.launch.dryrun --all            # subprocess per combo
# --------------------------------------------------------------------------

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ALIASES, get_config  # noqa: E402
from repro.core import FedConfig  # noqa: E402
from repro.core.fedlrt import fedlrt_round  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import data_axis_size, make_production_mesh  # noqa: E402
from repro.launch.specs import (  # noqa: E402
    SHAPES,
    decode_specs,
    prefill_specs,
    shape_applies,
    train_specs,
)
from repro.models import build_model, sharding  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "../../../results/dryrun")


def param_shapes_and_specs(model):
    """Abstract init: ShapeDtypeStructs for params + the static spec tree."""
    box = {}

    def f(k):
        p, s = model.init(k)
        box["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return shapes, box["specs"]


def _named(mesh, spec_tree):
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool, s_star: int = 4,
                correction: str = "simplified", overrides=None,
                method: str = "fedlrt"):
    cfg = get_config(arch)
    if method in ("fedlin", "fedavg"):
        # dense baseline: same model, low-rank factorization disabled
        from repro.models.config import LowRankPolicy

        cfg = dataclasses.replace(cfg, lowrank=LowRankPolicy(enable=False))
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, reason = shape_applies(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    sharding.enable(mesh)
    model = build_model(cfg)
    pshapes, pspecs = param_shapes_and_specs(model)
    from repro.launch.specs import sanitize_specs

    pspecs = sanitize_specs(mesh, pshapes, pspecs)
    pshard = _named(mesh, pspecs)

    t0 = time.time()
    if shape.kind == "train":
        C = data_axis_size(mesh)
        bstructs, bspecs = train_specs(cfg, shape, C, mesh)
        # repro-lint: disable=RPL002 -- offline lowering probe: builds a
        # throwaway FedConfig purely to trace shapes/HLO, never to run a
        # scenario (no data, no engine, nothing to spec-hash)
        fc = FedConfig(
            num_clients=C, s_star=s_star, lr=1e-2, correction=correction,
            tau=0.01, eval_after=False,
        )

        from repro.launch.specs import _batch_axes

        sharding.set_client_mode(True)  # client dim owns the data axes

        if method == "fedlrt":
            def step(params, batch):
                return fedlrt_round(
                    model.loss_fn, params, batch, fc, spec_tree=pspecs,
                    client_axes=_batch_axes(mesh),
                )
        else:
            from repro.core.baselines import fedavg_round, fedlin_round

            base_fn = fedlin_round if method == "fedlin" else fedavg_round

            def step(params, batch):
                return base_fn(model.loss_fn, params, batch, fc)

        lowered = jax.jit(
            step,
            in_shardings=(pshard, _named(mesh, bspecs)),
            out_shardings=(pshard, None),
        ).lower(pshapes, bstructs)
    elif shape.kind == "prefill":
        bstructs, bspecs = prefill_specs(cfg, shape, mesh)

        def step(params, batch):
            return model.serve_prefill(params, batch, cache_len=shape.seq_len)

        lowered = jax.jit(
            step, in_shardings=(pshard, _named(mesh, bspecs))
        ).lower(pshapes, bstructs)
    else:  # decode
        (cstructs, tokens), (cspecs, tok_spec) = decode_specs(cfg, model, shape, mesh)
        lowered = jax.jit(
            model.serve_step,
            in_shardings=(pshard, _named(mesh, cspecs),
                          jax.sharding.NamedSharding(mesh, tok_spec)),
        ).lower(pshapes, cstructs, tokens)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    roof = rl.from_compiled(compiled)
    tokens_total = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1
    )
    mflops = rl.model_flops(cfg, tokens_total, backward=(shape.kind == "train"))
    if shape.kind == "train":
        # the FeDLRT round does (1 basis-grad + s_star coeff) fwd+bwd passes
        mflops = mflops * (1 + s_star)
    n_dev = mesh.devices.size
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": int(n_dev),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "roofline": roof.to_dict(),
        "model_flops_total": mflops,
        "model_flops_per_device": mflops / n_dev,
        "useful_flops_ratio": (
            (mflops / n_dev) / roof.flops_per_device
            if roof.flops_per_device else None
        ),
    }


def run_one(args):
    res = lower_combo(
        args.arch, args.shape, multi_pod=args.multi_pod, s_star=args.s_star,
        correction=args.correction, method=args.method,
    )
    res["method"] = args.method
    outdir = os.path.abspath(args.out or RESULTS_DIR)
    os.makedirs(outdir, exist_ok=True)
    suffix = "" if args.method == "fedlrt" else f"__{args.method}"
    tag = f"{res.get('mesh', 'skip')}__{args.arch}__{args.shape}{suffix}.json"
    with open(os.path.join(outdir, tag), "w") as f:
        json.dump(res, f, indent=2)
    if "skipped" in res:
        print(f"SKIP  {args.arch} × {args.shape}: {res['skipped']}")
        return
    r = res["roofline"]
    print(
        f"OK    {args.arch} × {args.shape} [{res['mesh']}] "
        f"compile={res['compile_s']}s "
        f"compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
        f"collective={r['collective_s']*1e3:.2f}ms dominant={r['dominant']} "
        f"temp={res['memory']['temp_bytes']/2**30:.2f}GiB/dev"
    )


def run_all(args):
    combos = []
    for arch in ALIASES:
        for shape in SHAPES:
            combos.append((arch, shape, False))
            if args.multi_pod_all:
                combos.append((arch, shape, True))
    failures = []
    for arch, shape, mp in combos:
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape,
        ] + (["--multi-pod"] if mp else []) + (
            ["--out", args.out] if args.out else []
        )
        t0 = time.time()
        p = subprocess.run(cmd, capture_output=True, text=True,
                           env=dict(os.environ, PYTHONPATH="src"))
        sys.stdout.write(p.stdout)
        if p.returncode != 0:
            failures.append((arch, shape, mp))
            print(f"FAIL  {arch} × {shape} mp={mp} ({time.time()-t0:.0f}s)")
            sys.stderr.write(p.stderr[-2000:])
    print(f"\n{len(combos) - len(failures)}/{len(combos)} combos OK")
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser(description="FeDLRT multi-pod dry-run")
    ap.add_argument("--arch", type=str, default="qwen2-7b")
    ap.add_argument("--shape", type=str, default="train_4k", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--multi-pod-all", action="store_true")
    ap.add_argument("--s-star", type=int, default=4)
    ap.add_argument("--correction", type=str, default="simplified")
    ap.add_argument(
        "--method", type=str, default="fedlrt",
        choices=["fedlrt", "fedlin", "fedavg"],
        help="fedlin/fedavg lower the dense full-rank baseline round",
    )
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()
    if args.all:
        sys.exit(run_all(args))
    run_one(args)


if __name__ == "__main__":
    main()
