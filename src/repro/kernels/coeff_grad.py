"""Pallas TPU kernel for the coefficient-gradient projection ``C = Aᵀ B``.

With ``A = x Ũ`` and ``B = (∂L/∂y) Ṽ`` this computes the FeDLRT client's
per-step coefficient gradient ``∇_S̃ L = Aᵀ B`` (the backward hot spot of
the local loop).  Also reused for the basis cotangents ``dU = xᵀ(dy V Sᵀ)``
where the output's leading dim is large — hence the (K, M) grid with the
reduction over M tiles innermost and an f32 VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 512
DEFAULT_BKA = 256


def _atb_kernel(a_ref, b_ref, c_ref, acc_ref, *, nm: int):
    """grid = (ki, mi): C[ki] = Σ_mi A[mi, ki]ᵀ @ B[mi]."""
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...],
        b_ref[...],
        (((0,), (0,)), ((), ())),  # contract over the M (rows) dim
        preferred_element_type=jnp.float32,
    )

    @pl.when(mi == nm - 1)
    def _write():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def atb(
    A: jax.Array,
    B: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bka: int = DEFAULT_BKA,
    interpret: bool = False,
) -> jax.Array:
    """C = Aᵀ @ B.  A: (M, Ka), B: (M, Kb) → C: (Ka, Kb), f32 accumulate."""
    M, Ka = A.shape
    Kb = B.shape[1]
    bm, bka = min(bm, M), min(bka, Ka)
    assert M % bm == 0 and Ka % bka == 0, (M, Ka, bm, bka)
    # deferred import: lowrank_matmul owns the tile guard (and shares the
    # constraint table in repro.kernels.constraints with the RPL009 linter)
    from repro.kernels.lowrank_matmul import _check_tiles

    _check_tiles(interpret, A.dtype, bm=(bm, "sublane"), bka=(bka, "lane"),
                 Kb=(Kb, "lane"))
    nm = M // bm
    return pl.pallas_call(
        functools.partial(_atb_kernel, nm=nm),
        grid=(Ka // bka, nm),
        in_specs=[
            pl.BlockSpec((bm, bka), lambda ki, mi: (mi, ki)),
            pl.BlockSpec((bm, Kb), lambda ki, mi: (mi, 0)),
        ],
        out_specs=pl.BlockSpec((bka, Kb), lambda ki, mi: (ki, 0)),
        out_shape=jax.ShapeDtypeStruct((Ka, Kb), A.dtype),
        scratch_shapes=[pltpu.VMEM((bka, Kb), jnp.float32)],
        interpret=interpret,
        name="atb",
    )(A, B)
