"""Roofline-term derivation from a compiled dry-run artifact.

    compute term    = HLO_FLOPs_per_device / peak_FLOPs
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_wire_bytes_per_device / (links · link_bw)

``cost_analysis`` supplies FLOPs and bytes (already per-partition for SPMD
modules).  Collective bytes are NOT in cost_analysis: we parse the
optimized HLO text and apply the standard ring-cost model per collective
kind (sizes are the per-device shard sizes printed in SPMD HLO).

Hardware constants: TPU v5e-class — 197 TFLOP/s bf16 per chip, 819 GB/s
HBM, ~50 GB/s/link ICI (per direction, ~3 usable links/chip on a 2-D torus;
we report per-link seconds with links=1 so the term is conservative).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|f8\w*|s\d+|u\d+|c\d+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        key = dt if dt in _DTYPE_BYTES else ("f8" if dt.startswith("f8") else dt)
        total += n * _DTYPE_BYTES.get(key, 4)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device wire bytes by collective kind (ring cost model).

    SPMD HLO shapes are per-partition.  Wire cost per device:
      all-reduce       2·S·(n-1)/n ≈ 2·S     (S = result shard size)
      all-gather       S_out·(n-1)/n ≈ S_out (result = gathered shard)
      reduce-scatter   S_in·(n-1)/n ≈ S_in   (operand = pre-scatter shard)
      all-to-all       S·(n-1)/n ≈ S
      collective-permute  S
    We approximate (n-1)/n ≈ 1 (n ≥ 16 on the assigned meshes).
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    out["total"] = 0.0
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        result_shape, kind = m.group(1), m.group(2)
        if "-done(" in line:
            continue  # count the -start only
        size = _shape_bytes(result_shape)
        if kind == "all-reduce":
            wire = 2.0 * size
        else:
            wire = float(size)
        out[kind] += wire
        out["total"] += wire
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collectives: Dict[str, float]

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "collectives": self.collectives,
        }


def from_compiled(compiled) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    return Roofline(
        flops_per_device=flops,
        bytes_per_device=bytes_acc,
        collective_bytes_per_device=coll["total"],
        collectives={k: v for k, v in coll.items() if k != "total"},
    )


def model_flops(cfg, tokens: int, *, backward: bool) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE), N = active params.

    Uses the *factorized* parameter count when low-rank is enabled — the
    useful work of the compressed model."""
    from repro.models import build_model
    import jax

    model = build_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.ShapeDtypeStruct((2,), "uint32"))

    def leaf_params(path, leaf):
        name = jax.tree_util.keystr(path)
        size = 1
        for d in leaf.shape:
            size *= d
        if "moe" in name and ("'up'" in name or "'down'" in name or "'gate'" in name) \
                and "shared" not in name:
            # routed experts: only top_k/E of them are active per token
            size = size * cfg.moe.top_k // cfg.moe.num_experts
        return size

    import jax.tree_util as jtu
    total = sum(
        leaf_params(p, l) for p, l in jtu.tree_leaves_with_path(shapes)
        if hasattr(l, "shape")
    )
    mult = 6.0 if backward else 2.0
    return mult * total * tokens
