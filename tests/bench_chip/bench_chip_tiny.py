"""A benchmark tree at a size the CPU holds, for the benchmark's tests.

It copies the real tree (code, readers, peaks) and adds one cell,
``tiny.serve``: the registry's reduced Qwen2 (2 layers, width 256,
float32, the jnp chain) served under a short open-loop mix.  Its limit is
for float32 on the CPU, where the program and the reference agree to
round-off.  :func:`run_cell` runs a cell on the CPU: it stands in for
the chip (any device is taken, the compile cache is left off, and the
host's XLA operations stand in for a device's in a trace).
"""
from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path
from unittest import mock

REPO = Path(__file__).resolve().parents[2]
CHIP = REPO / "benchmarks" / "chip"

SERVE_CELL = "tiny.serve"

CONFIG = {
    "source": "https://huggingface.co/Qwen/Qwen2-7B/blob/main/config.json",
    "registry": "qwen2_7b",
    "smoke": True,
    "architecture": "dense_lm",
    "hidden_size": 256,
    "intermediate_size": 512,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "head_dim": 64,
    "num_hidden_layers": 2,
    "vocab_size": 512,
    "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0,
    "qkv_bias": True,
    "tie_word_embeddings": False,
    "lowrank": {"rank_frac": 0.25, "r_cap": 256, "min_dim": 32},
    "compute_dtype": "float32",
    "param_dtype": "float32",
    "basis_dtype": "float32",
    "kernels": "off",
    "reduced": [],
    "assumed": [],
}

SERVE_MIX = {
    "kind": "serve_open_loop",
    "rate_per_s": 8.0,
    "prompt_mean": 24, "prompt_sigma": 0.5, "prompt_min": 4, "prompt_max": 64,
    "output_mean": 8, "output_sigma": 0.5, "output_min": 2, "output_max": 16,
    "max_batch": 4, "max_queue": 64, "max_prompt": 64, "prompt_bucket": 16,
    "max_new_tokens": 16, "checked_tokens": 40,
}

SERVE_LIMITS = {"logit_gap": 1e-3}


def digest(arrays: dict) -> str:
    """sha256 of ``{name: array}``: each array's name, dtype, shape and
    bytes, in the order of the names."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for k in sorted(arrays):
        a = np.asarray(arrays[k])
        h.update(f"{k} {a.dtype} {a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def make_tree(dest: Path) -> tuple:
    """(tree root, manifest path) of a copy of the benchmark with the
    tiny cell added."""
    root = dest / "chip"
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    peaks = json.loads((root / "peaks.json").read_text())
    # made-up peaks so that the readers run on the CPU; never a device number
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    (root / "peaks.json").write_text(json.dumps(peaks))
    (root / "traffic" / "tiny-serve.json").write_text(json.dumps(SERVE_MIX))
    (root / "workloads" / f"{SERVE_CELL}.json").write_text(json.dumps(
        {"config": "tiny", "traffic": "tiny-serve", "limits": SERVE_LIMITS}
    ))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["workloads"].append(
        {"name": SERVE_CELL, "config": "tiny", "traffic": "tiny-serve", "chips": 1, "why": "test"}
    )
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        cells = m.get("workloads", [])
        if any(".serve." in c for c in cells):
            cells.append(SERVE_CELL)
    path = dest / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return root, path


def load_cpu_trace(path, load=None):
    """``trace.load`` (or ``load``), with the host's XLA operations (those
    that carry an ``hlo_op`` stat) as the one device's: a CPU trace has no
    device plane."""
    from jax.profiler import ProfileData

    from benchmarks.chip import trace

    tr = (load or trace.load)(path)
    found = sorted(Path(path).rglob("*.xplane.pb")) if Path(path).is_dir() else [Path(path)]
    ops = []
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    try:
                        stats = dict(e.stats)
                    except (TypeError, ValueError):
                        stats = {}
                    if e.duration_ns > 0 and "hlo_op" in stats:
                        ops.append((e.name, float(e.start_ns), float(e.duration_ns)))
    tr.device_ops.setdefault("/host:CPU", ops)
    return tr


def run_cell(root: Path, manifest: Path, *, seed: int = 7, seconds: float = 2.0,
             trace: int = 0, cell: str = SERVE_CELL):
    """Run ``run.main`` on the CPU; returns (exit code, result or None, stderr)."""
    from benchmarks.chip import harness, run
    from benchmarks.chip import trace as trace_mod

    out, err = io.StringIO(), io.StringIO()
    bench = harness.Bench(root, manifest)
    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            run, "find_chips", lambda jax_mod, chips: jax_mod.devices()[:chips]))
        stack.enter_context(mock.patch.object(run, "enable_cache", lambda jax_mod: "off"))
        real = trace_mod.load
        stack.enter_context(mock.patch.object(
            trace_mod, "load", lambda path: load_cpu_trace(path, real)))
        stack.enter_context(contextlib.redirect_stdout(out))
        stack.enter_context(contextlib.redirect_stderr(err))
        rc = run.main(
            ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace)],
            bench=bench,
        )
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None), err.getvalue()
