"""Operations and bytes of the served work, counted from shapes, and the
chip's published peaks.

Whatever implements the work, these counts stay as they are: a later
change that replaces a kernel leaves them unchanged.  Shapes come from a
configuration file; what one decode step requires is counted by the
configuration's architecture file (``harness.architecture``), from the
per-factor counts here.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List

from benchmarks.chip import harness
from benchmarks.chip.layout import Linear

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def factor_cost(lin: Linear, rows: int, itemsize: int) -> tuple:
    """(flops, bytes) of ``((x U) S) Vᵀ`` on ``rows`` rows, in every layer
    of the stack: each factor is read once for all rows."""
    n = max(lin.layers, 1)
    flops = n * rows * 2 * (lin.n_in * lin.r + lin.r * lin.r + lin.r * lin.n_out)
    nbytes = n * itemsize * (lin.n_in * lin.r + lin.r * lin.r + lin.n_out * lin.r)
    return flops, nbytes


def embed_cost(lin: Linear, rows: int, itemsize: int) -> tuple:
    """(flops, bytes) of the embedding rows ``U[t] S Vᵀ`` of ``rows``
    tokens: one row of U per token, S and V once."""
    n = max(lin.layers, 1)
    flops = n * rows * 2 * (lin.r * lin.r + lin.r * lin.n_out)
    nbytes = n * itemsize * (rows * lin.r + lin.r * lin.r + lin.n_out * lin.r)
    return flops, nbytes


def decode_step_cost(cfg: dict, contexts: List[int], itemsize: int = 2,
                     root: Path = harness.ROOT) -> tuple:
    """(flops, bytes) one decode step requires for sequences whose state
    holds ``contexts[i]`` positions (the new token's included), as
    ``cfg``'s architecture file (found under ``root``) counts them."""
    return harness.architecture(cfg, root).decode_step_cost(cfg, contexts, itemsize)
