"""Operations and bytes of the served work, counted from shapes, and the
chip's published peaks.

Whatever implements the work, these counts stay as they are: a later
change that replaces a kernel leaves them unchanged.  Shapes come from a
configuration file (``layout.py``).  :func:`decode_step_cost` gives the
FLOPs and bytes one decode step requires: the factors once, the KV cache
up to the positions read.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List

from benchmarks.chip import layout

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


def decode_step_cost(cfg: dict, contexts: List[int], itemsize: int = 2):
    """(flops, bytes) one decode step requires for sequences whose KV
    holds ``contexts[i]`` positions (the new token's included).

    Every factor is read once for the batch; each sequence reads its own
    keys and values up to its position.  The embedding reads one row of U
    per sequence.
    """
    B = len(contexts)
    Hkv, hd, L = cfg["num_key_value_heads"], layout.head_dim(cfg), cfg["num_hidden_layers"]
    H = cfg["num_attention_heads"]
    flops, nbytes = 0, 0
    for lin in layout.linears(cfg):
        n = max(lin.layers, 1)
        if lin.path == "embed":
            flops += n * B * 2 * (lin.r * lin.r + lin.r * lin.n_out)
            nbytes += n * itemsize * (B * lin.r + lin.r * lin.r + lin.n_out * lin.r)
            continue
        flops += n * B * 2 * (lin.n_in * lin.r + lin.r * lin.r + lin.r * lin.n_out)
        nbytes += n * itemsize * (lin.n_in * lin.r + lin.r * lin.r + lin.n_out * lin.r)
    ctx = sum(contexts)
    flops += L * 2 * (2 * hd * H * ctx)
    nbytes += L * itemsize * 2 * Hkv * hd * ctx
    return flops, nbytes
