"""Pallas TPU flash attention (online-softmax, VMEM-tiled).

Addresses the dominant *memory* roofline term of the train/prefill shapes
(EXPERIMENTS.md §Perf, pair 1): the jnp attention materializes
``(B, H, Tq, Tk)`` scores in HBM; this kernel streams KV blocks through
VMEM keeping only ``(bq, bk)`` score tiles and the running max/sum
(Rabe-Staats/FlashAttention recurrence), so HBM traffic drops from
O(T²) to O(T·d).

Grid: ``(B·H, Tq/bq, Tk/bk)`` — the KV dim is innermost so the f32
accumulator, running max ``m`` and sum ``l`` persist in VMEM scratch
across the KV sweep of each query tile.  Causal + sliding-window masking
is evaluated from absolute positions, so the same kernel serves ragged
decode layouts.  MXU alignment: ``bq``,``bk`` multiples of 128 lanes /
8 sublanes; head_dim padded by the ops wrapper if needed.

Supports MHA/GQA via a ``q_head → kv_head`` map folded into the grid.
Validated in interpret mode against :func:`repro.kernels.ref.mha_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(
    qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, nk: int, causal: bool, window: int, scale: float,
):
    """One (batch·head, q-tile, kv-tile) grid step."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    v = v_ref[0]  # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    qp = qpos_ref[0]  # (bq,)
    kp = kpos_ref[0]  # (bk,)
    mask = (kp[None, :] >= 0) & (qp[:, None] >= 0)
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window:
        mask &= kp[None, :] > qp[:, None] - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m == -inf): exp(-inf - -inf) would be nan
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev) - m_safe)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kk == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    causal: bool = True,
    sliding_window: int = 0,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Tq, H, d); k/v: (B, Tk, Hkv, d) → (B, Tq, H, d).

    GQA: H must be a multiple of Hkv; query head h reads kv head
    ``h // (H // Hkv)``.  Positions are absolute (negative = invalid slot).
    """
    B, Tq, H, d = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / (d ** 0.5)

    bq = min(bq, Tq)
    bk = min(bk, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, Tk, bq, bk)
    nq, nk = Tq // bq, Tk // bk

    # layout: (B·H, T, d) with positions broadcast per row-block
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, d)
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(B * H, Tk, d)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(B * H, Tk, d)
    qp = jnp.broadcast_to(
        q_positions.astype(jnp.int32)[None], (B * H, Tq)
    )
    kp = jnp.broadcast_to(
        kv_positions.astype(jnp.int32)[None], (B * H, Tk)
    )

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, nk=nk, causal=causal, window=sliding_window,
            scale=scale,
        ),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq), lambda bh, qi, ki: (bh, qi)),
            pl.BlockSpec((1, bk), lambda bh, qi, ki: (bh, ki)),
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, qr, kr, vr)
    return out.reshape(B, H, Tq, d).transpose(0, 2, 1, 3)
