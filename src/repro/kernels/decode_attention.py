"""Pallas TPU decode attention that reads one layer of a KV-cache stack in place.

A decode step (one new token per sequence) carries the whole cache stack
``(NB, B, Hkv, S, hd)`` through the layer scan and writes each layer's new
rows into it in place.  XLA gives its attention dot a dynamic slice of the
stack, which it materialises: a copy of the layer's keys and values in
every layer of every step.  This kernel takes the layer index as a
scalar-prefetch argument instead, so each grid step's BlockSpec DMA reads
layer ``layer``, sequence ``b`` straight out of the stack.

The cache is head-major: each kv head's positions are one contiguous
``(S, hd)`` block, so the query heads of a group score their own kv head
with one plain dot.  Grid: ``(B, S // block)``; a step holds ``block``
positions of every kv head of one sequence, and the softmax runs online
over the position blocks in float32 (running max and sum in scratch), so
any slot length fits in VMEM.  Validated in interpret mode against
:func:`repro.models.layers.attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: the most one grid step's keys (or values) may take in VMEM; both are
#: double-buffered, so four such blocks are live
MAX_BLOCK_BYTES = 2 * 2**20
LANES = 128


def block_len(S: int, Hkv: int, hd: int, itemsize: int) -> int:
    """Positions a grid step reads: the whole slot where it fits, else the
    largest multiple of 128 that divides ``S`` and fits; 0 where none does
    (a slot too long to read whole whose length is no multiple of 128)."""
    lanes = -(-hd // LANES) * LANES  # VMEM pads a row to whole lanes
    fits = lambda n: Hkv * n * lanes * itemsize <= MAX_BLOCK_BYTES
    if fits(S):
        return S
    n = S - S % LANES
    while n and (not fits(n) or S % n):
        n -= LANES
    return n


def _decode_kernel(layer_ref, q_ref, mask_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale):
    del layer_ref  # read by the index maps
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    keep = mask_ref[0] != 0  # (1, block)
    for j in range(k_ref.shape[0]):  # kv heads
        s = jax.lax.dot_general(
            q_ref[0, j], k_ref[j], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (G, block)
        s = jnp.where(keep, s * scale, NEG_INF)
        m_prev = m_ref[j]  # (G, LANES), every lane the same
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - jnp.max(m_next, axis=-1, keepdims=True))
        m_ref[j] = m_next
        l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(v_ref.dtype), v_ref[j],
                     preferred_element_type=jnp.float32)
        acc_ref[j] = jnp.max(alpha, axis=-1, keepdims=True) * acc_ref[j] + pv

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        for j in range(k_ref.shape[0]):
            inv = 1.0 / jnp.max(l_ref[j], axis=-1, keepdims=True)
            o_ref[0, j] = (acc_ref[j] * inv).astype(o_ref.dtype)


def decode_attention(q, mask, k_stack, v_stack, layer, *, interpret=False):
    """One new token per sequence against layer ``layer`` of the stacks.

    q: (B, H, hd); mask: (B, S) bool, the keys each sequence may attend;
    k_stack, v_stack: (NB, B, Hkv, S, hd); layer: traced int32 scalar.
    Query head h reads kv head ``h // (H // Hkv)``.  Returns (B, H, hd) in
    ``q``'s dtype.  Callers check that :func:`block_len` finds a block.
    """
    B, H, hd = q.shape
    NB, _, Hkv, S, _ = k_stack.shape
    G = H // Hkv
    n = block_len(S, Hkv, hd, k_stack.dtype.itemsize)
    # the score scale as the jnp path rounds its divisor: sqrt(hd) in q's dtype
    with jax.ensure_compile_time_eval():
        scale = 1.0 / float(jnp.sqrt(hd).astype(q.dtype))
    heads = pl.BlockSpec((1, Hkv, G, hd), lambda b, c, lr: (b, 0, 0, 0))
    kv = pl.BlockSpec((None, None, Hkv, n, hd), lambda b, c, lr: (lr[0], b, 0, c, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S // n),
            in_specs=[heads, pl.BlockSpec((1, 1, n), lambda b, c, lr: (b, 0, c)),
                      kv, kv],
            out_specs=heads,
            scratch_shapes=[pltpu.VMEM((Hkv, G, LANES), jnp.float32),
                            pltpu.VMEM((Hkv, G, LANES), jnp.float32),
                            pltpu.VMEM((Hkv, G, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), q.reshape(B, Hkv, G, hd),
        mask.astype(jnp.int32)[:, None], k_stack, v_stack,
    )
    return out.reshape(B, H, hd)
