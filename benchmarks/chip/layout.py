"""Generic helpers for the shapes of a model's factorized weights.

The benchmark's weight generator, its plain reference and its FLOP and
byte counts read a model's shapes from its architecture file
(``references/<architecture>.py``, found by ``harness.architecture``),
never from the program.  That file lists the factorized weights as
:class:`Linear` s (built with :func:`factorized`) and every other leaf as
(path, shape, init); see ``harness.py`` for all it provides.  Paths name
parameters as ``a/b/c`` strings; block parameters carry a leading layer
axis.
"""
from __future__ import annotations

import dataclasses


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


def factorized(cfg: dict, path: str, n_in: int, n_out: int, layers: int = 0) -> Linear:
    """The :class:`Linear` at ``path``, refused where the program's policy
    (``lowrank.min_dim``) would keep the matrix dense: such a matrix is
    one of the architecture's other leaves."""
    if min(n_in, n_out) < cfg["lowrank"]["min_dim"]:
        raise ValueError(
            f"{path}: {n_in}x{n_out} would stay dense; list it among the "
            f"architecture's other leaves"
        )
    return Linear(path, n_in, n_out, r_max(cfg, n_in, n_out), layers)
