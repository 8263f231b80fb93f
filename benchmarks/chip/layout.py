"""Shapes of a dense decoder's factorized weights, from a configuration file.

The benchmark's weight generator, its plain reference and its FLOP and
byte counts all read the model's shapes from here, never from the
program.  Paths name parameters as ``a/b/c`` strings; block parameters
carry a leading layer axis.
"""
from __future__ import annotations

import dataclasses
from typing import List


@dataclasses.dataclass(frozen=True)
class Linear:
    """One factorized ``n_in → n_out`` weight ``U S Vᵀ`` of rank buffer ``r``."""

    path: str
    n_in: int
    n_out: int
    r: int
    layers: int  # 0: not stacked

    @property
    def stack(self) -> tuple:
        return (self.layers,) if self.layers else ()


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def r_max(cfg: dict, n_in: int, n_out: int) -> int:
    """FeDLRT rank buffer: ``rank_frac · min(n)`` capped at ``r_cap`` and at
    half the smaller side (the augmented basis must fit), rounded up to a
    multiple of 8."""
    pol = cfg["lowrank"]
    small = min(n_in, n_out)
    r = min(int(pol["rank_frac"] * small), pol["r_cap"], small // 2)
    r = max(8 * ((r + 7) // 8), 1)
    return min(r, max(small // 2, 1))


def linears(cfg: dict) -> List[Linear]:
    """Every factorized weight of the model, embedding and head included."""
    d, dff = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    V, L = cfg["vocab_size"], cfg["num_hidden_layers"]
    shapes = [
        ("embed", V, d, 0),
        ("lm_head", d, V, 0),
        ("blocks/attn/q", d, H * hd, L),
        ("blocks/attn/k", d, Hkv * hd, L),
        ("blocks/attn/v", d, Hkv * hd, L),
        ("blocks/attn/o", H * hd, d, L),
        ("blocks/mlp/gate", d, dff, L),
        ("blocks/mlp/up", d, dff, L),
        ("blocks/mlp/down", dff, d, L),
    ]
    out = []
    for path, n_in, n_out, layers in shapes:
        if min(n_in, n_out) < cfg["lowrank"]["min_dim"]:
            raise ValueError(
                f"{path}: {n_in}x{n_out} would stay dense; the benchmark's "
                f"reference covers factorized weights only"
            )
        out.append(Linear(path, n_in, n_out, r_max(cfg, n_in, n_out), layers))
    return out


def dense_leaves(cfg: dict) -> List[tuple]:
    """(path, shape, init value) of the dense leaves: norm scales, QKV bias."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    out = [
        ("final_norm", (d,), 1.0),
        ("blocks/ln1", (L, d), 1.0),
        ("blocks/ln2", (L, d), 1.0),
    ]
    if cfg["qkv_bias"]:
        out += [
            ("blocks/attn/q_b", (L, H * hd), 0.0),
            ("blocks/attn/k_b", (L, Hkv * hd), 0.0),
            ("blocks/attn/v_b", (L, Hkv * hd), 0.0),
        ]
    return out
