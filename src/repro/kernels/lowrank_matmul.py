"""Pallas TPU kernels for the low-rank bottleneck chain ``y = x U S Vᵀ``.

Design (TPU-native, not a CUDA port):
- The chain never materializes the ``n_in × n_out`` weight; HBM traffic is
  ``O(M·(n_in + n_out) + (n_in + n_out)·r)`` instead of ``O(n_in·n_out)``.
- :func:`xus` fuses the first two matmuls: grid over (M, K) tiles, f32
  accumulation of ``x·U`` in VMEM scratch, multiply by the small ``S`` in
  the epilogue of the last K step — one HBM pass over ``x``.
- :func:`avt` is a plain (M, N)-tiled matmul against ``Vᵀ`` with the rank
  dim fully resident.
- The rank dim is padded to a multiple of 128 lanes by the ops wrapper;
  padded columns are zero, so results are exact.  MXU alignment: all tile
  dims are multiples of (8, 128) for f32 and (16, 128) for bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.constraints import LANE, SUBLANE_BY_ITEMSIZE

DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256


def _min_sublane(dtype) -> int:
    """MXU minimum second-to-minor tile dim: 8 (f32) / 16 (bf16) / 32 (i8).

    The numbers live in :mod:`repro.kernels.constraints` (shared with the
    static analyzer's RPL009 shape interpreter); this wrapper only resolves
    the jax dtype object to its byte width.
    """
    return SUBLANE_BY_ITEMSIZE.get(jnp.dtype(dtype).itemsize, 8)


def _check_tiles(interpret: bool, dtype, **tiles):
    """On the compiled TPU path, reject tile dims the MXU cannot address:
    sublane dims must be multiples of the dtype minimum, lane dims of 128.
    Interpret mode (CPU validation) is exempt — it has no tiling hardware.
    """
    if interpret:
        return
    sub = _min_sublane(dtype)
    for name, (size, kind) in tiles.items():
        mult = LANE if kind == "lane" else sub
        if size % mult:
            raise ValueError(
                f"{name}={size} is not a multiple of {mult} "
                f"({kind} dim, dtype {jnp.dtype(dtype).name})"
            )


def _xus_kernel(x_ref, u_ref, s_ref, a_ref, acc_ref, *, nk: int):
    """grid = (mi, kk).  acc (bm, R) persists across the K loop."""
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], u_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(kk == nk - 1)
    def _epilogue():
        a_ref[...] = jnp.dot(
            acc_ref[...], s_ref[...], preferred_element_type=jnp.float32
        ).astype(a_ref.dtype)


def xus(x: jax.Array, U: jax.Array, S: jax.Array, *, bm: int = DEFAULT_BM,
        bk: int = DEFAULT_BK, interpret: bool = False) -> jax.Array:
    """A = (x @ U) @ S.  x: (M, K), U: (K, R), S: (R, R) → A: (M, R)."""
    M, K = x.shape
    R = U.shape[1]
    bm, bk = min(bm, M), min(bk, K)
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    _check_tiles(interpret, x.dtype, bm=(bm, "sublane"), bk=(bk, "lane"),
                 R=(R, "lane"))
    nk = K // bk
    grid = (M // bm, nk)
    return pl.pallas_call(
        functools.partial(_xus_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, kk: (mi, kk)),
            pl.BlockSpec((bk, R), lambda mi, kk: (kk, 0)),
            pl.BlockSpec((R, R), lambda mi, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, R), lambda mi, kk: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((M, R), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, R), jnp.float32)],
        interpret=interpret,
        name="xus",
    )(x, U, S.astype(jnp.float32))


def _avt_kernel(a_ref, v_ref, y_ref):
    """grid = (mi, nj): y tile = A tile @ V tileᵀ."""
    y_ref[...] = jax.lax.dot_general(
        a_ref[...],
        v_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)


def avt(A: jax.Array, V: jax.Array, *, bm: int = DEFAULT_BM,
        bn: int = DEFAULT_BN, interpret: bool = False) -> jax.Array:
    """y = A @ Vᵀ.  A: (M, R), V: (N, R) → y: (M, N)."""
    M, R = A.shape
    N = V.shape[0]
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    _check_tiles(interpret, A.dtype, bm=(bm, "sublane"), bn=(bn, "lane"),
                 R=(R, "lane"))
    return pl.pallas_call(
        _avt_kernel,
        grid=(M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, R), lambda mi, nj: (mi, 0)),
            pl.BlockSpec((bn, R), lambda mi, nj: (nj, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, nj: (mi, nj)),
        out_shape=jax.ShapeDtypeStruct((M, N), A.dtype),
        interpret=interpret,
        name="avt",
    )(A, V)
