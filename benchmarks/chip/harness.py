"""Finds a cell's files by name and assembles its result line.

A benchmark tree (``benchmarks/chip`` by default, any directory in tests)
holds ``workloads/<cell>.json``, ``configs/<config>.json``,
``traffic/<mix>.json``, ``traffic/<kind>.py``, ``metrics/<name>.py`` and
``references/<architecture>.py``; ``BENCHMARK.json`` says which
end-to-end and per-layer metrics each cell reports.  Adding a cell, a
configuration, a mix, a per-layer metric or an architecture adds files
and touches no code.

A configuration's ``architecture`` names its architecture file
(:func:`architecture`), which holds everything the benchmark knows of the
model's shapes and math (module-level functions, each taking the
configuration as read):

- ``linears(cfg)``: the factorized weights, ``layout.Linear``s in the
  order their seeds are drawn;
- ``leaves(cfg)``: every other leaf as (path, shape, init), where init is
  a number or a function of (key, shape) that draws from the seed;
- ``program_fields(cfg)``: the program's model-config fields (dotted
  attribute paths) that must equal the file's, beside the generic checks
  of ``program.check_config``;
- ``program_path(names)``: the benchmark path of a leaf of the program's
  parameter pytree, from the pytree's key names;
- ``logits(cfg, w, inputs, mode)``: the plain reference's float32 logits
  for token ids, its products in ``mode`` (``references/arith.py``);
- ``decode_step_cost(cfg, contexts, itemsize)``: the (FLOPs, bytes) one
  decode step requires (``counts.py`` has per-factor helpers).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parents[1]
MANIFEST = CHECKOUT / "BENCHMARK.json"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path, name: str) -> ModuleType:
    """Import one file of the tree by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(f"_bench_chip.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _architecture_file(path: Path, name: str) -> ModuleType:
    return load_module(path, name)


def architecture(cfg: dict, root: Path = ROOT) -> ModuleType:
    """The architecture file that ``cfg["architecture"]`` names, loaded
    once per path: ``<root>/references/<architecture>.py``."""
    name = cfg["architecture"]
    path = Path(root) / "references" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"the configuration's architecture {name!r} has no file: {path}")
    return _architecture_file(path, name)


@dataclasses.dataclass
class Cell:
    """One workload with everything it names, loaded."""

    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT  # the tree its architecture file is found in


class Bench:
    """A benchmark tree and its manifest."""

    def __init__(self, root: Path = ROOT, manifest: Path = MANIFEST):
        self.root = Path(root)
        self.manifest = read_json(Path(manifest))

    def cell(self, name: str) -> Cell:
        wl = read_json(self.root / "workloads" / f"{name}.json")
        cfg = read_json(self.root / "configs" / f"{wl['config']}.json")
        mix = read_json(self.root / "traffic" / f"{wl['traffic']}.json")
        e2e = [m for m in self.manifest["end_to_end"] if _lists(m, name)]
        names = {m["name"] for m in e2e}
        per = [
            m for m in self.manifest["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in names)
        ]
        return Cell(name, wl, cfg, mix, e2e, per, self.root)

    def kind(self, kind: str) -> ModuleType:
        return load_module(self.root / "traffic" / f"{kind}.py", kind)

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.root / "metrics" / f"{metric}.py", metric)


def _lists(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileCounter:
    """Counts backend compiles (or persistent-cache loads) by phase."""

    def __init__(self):
        self.phase = "setup"
        self.seconds: Dict[str, float] = {}
        self.count: Dict[str, int] = {}

    def __call__(self, event: str, duration: float, **_):
        if event == COMPILE_EVENT:
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + duration
            self.count[self.phase] = self.count.get(self.phase, 0) + 1

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self


@dataclasses.dataclass
class Record:
    """What one run measured, for the metric readers and the result line."""

    cell: Cell
    seed: int
    device_kind: str
    peaks_file: Path = ROOT / "peaks.json"
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: Dict[str, dict] = dataclasses.field(default_factory=dict)
    # filled by a traced run
    trace: Optional[object] = None
    trace_window: Optional[tuple] = None
    extra: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks.values()
        )


def device_block(jax_mod, chips: int) -> dict:
    devs = jax_mod.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def result_line(rec: Record, trace: bool, device: dict, breakdown=None) -> dict:
    """The JSON object of the run's last line; ``checks`` comes last."""
    unit = {m["name"]: m["unit"] for m in rec.cell.end_to_end + rec.cell.per_layer}
    metrics = {}
    if not trace:
        values = dict(rec.end_to_end, setup_s=rec.setup_s)
        for m in rec.cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": unit[m["name"]]}
    else:
        for m in rec.cell.per_layer:
            value = rec.extra["per_layer"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": unit[m["name"]]}
    out = {
        "correct": rec.correct,
        "attempted": rec.attempted,
        "failed": rec.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = rec.checks
    return out


def print_checks(rec: Record) -> None:
    """The numbers compared, each beside its limit: the last lines of stderr."""
    for name, c in rec.checks.items():
        verdict = "ok" if math.isfinite(c["value"]) and c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)


class Session:
    """What a traffic driver gets from the harness: the run's arguments,
    and the window, which ends set-up and, in a traced run, is traced."""

    def __init__(self, rec: Record, *, seconds: float, trace: bool,
                 counter: CompileCounter, t_start: float, trace_dir: Optional[str] = None):
        self.rec, self.seconds, self.trace = rec, float(seconds), bool(trace)
        self.counter, self.t_start, self.trace_dir = counter, t_start, trace_dir

    def window(self):
        """Context manager around the measured window."""
        import contextlib
        import time

        import jax

        @contextlib.contextmanager
        def cm():
            if self.trace:
                jax.profiler.start_trace(self.trace_dir)
            self.rec.setup_s = time.perf_counter() - self.t_start
            self.counter.phase = "window"
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    yield
            finally:
                self.counter.phase = "after"
                if self.trace:
                    jax.profiler.stop_trace()

        return cm()
