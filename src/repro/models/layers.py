"""Layer primitives: parameter builder, maybe-factorized linears, norms,
rotary embeddings, and (blockwise) attention.

Every weight matrix goes through :meth:`Builder.linear`, which decides —
from the :class:`LowRankPolicy` — whether the layer is a FeDLRT-managed
:class:`LowRankFactor` or a plain dense array, and registers the matching
PartitionSpec.  Model code is agnostic: :func:`apply_linear` dispatches on
the leaf type.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.factorization import (
    LowRankFactor,
    init_factor,
    is_factor,
    lr_matmul,
)
from repro.kernels.ops import lowrank_apply_nd, use_kernels_for
from repro.models import sharding
from repro.models.config import LowRankPolicy

Array = jax.Array


# ---------------------------------------------------------------------------
# parameter builder
# ---------------------------------------------------------------------------


class Builder:
    """Collects (params, specs) as parallel nested dicts keyed by '/'-paths."""

    def __init__(self, key: Array, policy: LowRankPolicy, dtype=jnp.float32):
        self.policy = policy
        self.dtype = dtype
        self.params: dict = {}
        self.specs: dict = {}
        self._key = key

    def next_key(self) -> Array:
        self._key, k = jax.random.split(self._key)
        return k

    def _put(self, path: str, value, spec_leaf):
        parts = path.split("/")
        p, s = self.params, self.specs
        for part in parts[:-1]:
            p = p.setdefault(part, {})
            s = s.setdefault(part, {})
        assert parts[-1] not in p, f"duplicate param {path}"
        p[parts[-1]] = value
        s[parts[-1]] = spec_leaf

    def linear(
        self,
        path: str,
        n_in: int,
        n_out: int,
        *,
        li: Optional[str] = None,
        lo: Optional[str] = None,
        batch_shape: Tuple[int, ...] = (),
        batch_axes: Tuple[Optional[str], ...] = (),
        bias: bool = False,
        force_dense: bool = False,
        init_scale: Optional[float] = None,
    ):
        """A (possibly factorized) ``n_in → n_out`` weight at ``path``.

        ``batch_shape``/``batch_axes`` add leading stacking dims (layer
        stack, experts).  Returns nothing; parameters are collected.
        """
        assert len(batch_shape) == len(batch_axes)
        if self.policy.applies(n_in, n_out) and not force_dense:
            r_max = self.policy.r_max_for(n_in, n_out)
            init_rank = max(int(self.policy.init_rank_frac * r_max), 1)
            f = init_factor(
                self.next_key(),
                n_in,
                n_out,
                r_max,
                init_rank=init_rank,
                dtype=self.dtype,
                batch_shape=batch_shape,
            )
            self._put(path, f, sharding.factor_spec(batch_axes, li, lo))
        else:
            scale = init_scale if init_scale is not None else (2.0 / n_in) ** 0.5
            w = scale * jax.random.normal(
                self.next_key(), batch_shape + (n_in, n_out), dtype=self.dtype
            )
            # dense weights can use each mesh axis once: if both logical dims
            # resolve to the same axis (e.g. embed & ffn → model), keep the
            # output dim sharded (megatron convention)
            if sharding._resolve(li) is not None and sharding._resolve(
                li
            ) == sharding._resolve(lo):
                li = None
            self._put(path, w, sharding.spec(*batch_axes, li, lo))
        if bias:
            self._put(
                path + "_b",
                jnp.zeros(batch_shape + (n_out,), self.dtype),
                sharding.spec(*batch_axes, lo),
            )

    def vector(self, path: str, shape, *, axes=(), init: float = 1.0):
        v = jnp.full(shape, init, self.dtype)
        self._put(path, v, sharding.spec(*axes))

    def normal(self, path: str, shape, *, axes=(), scale: float = 0.02):
        v = scale * jax.random.normal(self.next_key(), shape, dtype=self.dtype)
        self._put(path, v, sharding.spec(*axes))

    def build(self):
        return self.params, self.specs


# ---------------------------------------------------------------------------
# apply helpers
# ---------------------------------------------------------------------------


def apply_linear(
    w,
    x: Array,
    *,
    bias: Optional[Array] = None,
    dtype=None,
    kernels: str = "off",
) -> Array:
    """``y = x @ W (+ b)`` dispatching on dense vs LowRankFactor leaves.

    ``kernels`` (a :data:`repro.kernels.KERNEL_POLICIES` policy, usually
    ``ModelConfig.kernels``) routes factor leaves — LowRankFactor *and* the
    client loop's 2r-wide AugmentedFactor — through the fused Pallas
    ``xus``/``avt`` chain with the ``atb``-backed custom VJP.  The
    augmented factors' active-direction masking survives the kernel path
    unchanged: inactive basis columns and coefficient blocks are exactly
    zero (factorization.py invariant), so the fused chain equals the
    masked reference chain.
    """
    dtype = dtype or x.dtype
    if is_factor(w):
        if kernels != "off":
            y = lowrank_apply_nd(
                x,
                w.U.astype(dtype),
                w.S.astype(dtype),
                w.V.astype(dtype),
                use_kernels_for(kernels),
            )
        else:
            # rank-bottleneck chain; never materializes the n_in×n_out matrix
            y = (
                jnp.matmul(jnp.matmul(x, w.U.astype(dtype)), w.S.astype(dtype))
                @ w.V.T.astype(dtype)
            )
    else:
        y = jnp.matmul(x, w.astype(dtype))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def apply_embedding(w, tokens: Array, *, dtype=jnp.float32, kernels: str = "off") -> Array:
    """Token embedding lookup (gather).

    The embedding factor's U is kept *replicated* (it is small once
    factorized: vocab × r), so the gather is local on every shard — a
    one-hot matmul against a vocab-sharded table would materialize a
    (B, T, vocab) temp, which dominated dry-run memory.

    Kernel path: the gathered rows ``u = U[tokens]`` play the activation
    role of the fused chain with the coefficient as the projection —
    ``((u S) I) Vᵀ`` — so ``y = u S Vᵀ`` reuses :func:`lowrank_apply_nd`'s
    custom VJP (dS arrives through the kernel's dU slot).
    """
    if is_factor(w):
        u = jnp.take(w.U, tokens, axis=0).astype(dtype)  # (..., r)
        if kernels != "off":
            eye = jnp.eye(w.S.shape[-1], dtype=dtype)
            return lowrank_apply_nd(
                u, w.S.astype(dtype), eye, w.V.astype(dtype),
                use_kernels_for(kernels),
            )
        return jnp.matmul(u, w.S.astype(dtype)) @ w.V.T.astype(dtype)
    return jnp.take(w, tokens, axis=0).astype(dtype)


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(T: int, d: int, dtype=jnp.float32) -> Array:
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    div = jnp.exp(
        jnp.arange(0, d, 2, dtype=jnp.float32) * (-jnp.log(10000.0) / d)
    )
    pe = jnp.zeros((T, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional sliding window, blockwise over query chunks)
# ---------------------------------------------------------------------------


def _gqa_scores(q: Array, k: Array) -> Array:
    """q: (B,Tq,H,hd), k: (B,Tk,Hkv,hd) → scores (B,H,Tq,Tk) with GQA."""
    B, Tq, H, hd = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Tq, Hkv, g, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) / jnp.sqrt(hd).astype(q.dtype)
    return s.reshape(B, Hkv * g, Tq, k.shape[1])


def _gqa_combine(p: Array, v: Array) -> Array:
    """p: (B,H,Tq,Tk), v: (B,Tk,Hkv,hd) → (B,Tq,H,hd)."""
    B, H, Tq, Tk = p.shape
    Hkv = v.shape[2]
    g = H // Hkv
    pg = p.reshape(B, Hkv, g, Tq, Tk)
    o = jnp.einsum("bkgqt,btkd->bqkgd", pg, v)
    return o.reshape(B, Tq, H, v.shape[-1])


def attention_mask(
    q_positions: Array, kv_positions: Array, *, causal: bool,
    sliding_window: int,
) -> Array:
    """Which keys each query may attend, from absolute positions.

    Negative kv positions mark never-written cache slots; the broadcasting
    form yields (Tq, Tk) for shared positions and (B, Tq, Tk) when either
    side is per-slot."""
    qpos, kpos = q_positions[..., :, None], kv_positions[..., None, :]
    m = (kpos >= 0) & (qpos >= 0)
    if causal:
        m &= kpos <= qpos
    if sliding_window:
        m &= kpos > qpos - sliding_window
    return m


def attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    q_positions: Array,
    kv_positions: Array,
    causal: bool = True,
    sliding_window: int = 0,
    q_chunk: int = 0,
) -> Array:
    """Masked dot-product attention, blockwise over query chunks.

    Blockwise evaluation bounds the live score tensor at
    ``(B, H, q_chunk, Tk)`` — O(T·chunk) memory for O(T²) compute — which
    is what lets prefill_32k lower within HBM on the target mesh.  The
    mask combines causality and an optional sliding window; ``positions``
    are absolute so the same code serves ragged decode (cache) layouts.

    Positions may carry a leading batch dim — ``q_positions`` ``(B, Tq)``
    and/or ``kv_positions`` ``(B, Tk)`` — for *per-slot* ragged decode
    (continuous batching: each cache row at its own sequence position).
    Batched positions take the single-block path; per-slot decode is
    ``Tq == 1``, so chunking never applies there anyway.
    """
    Tq = q.shape[1]
    q_chunk = q_chunk or Tq
    q_chunk = min(q_chunk, Tq)
    if q_positions.ndim > 1 or kv_positions.ndim > 1:
        q_chunk = Tq
    # Under sequence parallelism each shard already holds only Tq/msize
    # query rows; chunking below that size fights the sharding (the chunk
    # reshape forces per-iteration q gathers).  Skip chunking when the
    # per-shard score block is small enough.
    from repro.utils import meshctx

    if meshctx.mesh() is not None and "model" in meshctx.axis_names():
        local_rows = Tq // meshctx.axis_size("model")
        if 0 < local_rows <= q_chunk:
            q_chunk = Tq

    def block(qc, qpos):
        s = _gqa_scores(qc, k).astype(jnp.float32)  # (B,H,qc,Tk)
        m = attention_mask(qpos, kv_positions, causal=causal,
                           sliding_window=sliding_window)
        m = m[:, None] if m.ndim == 3 else m[None, None]
        s = jnp.where(m, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return _gqa_combine(p, v)

    if Tq == q_chunk:
        return block(q, q_positions)

    n_chunks = -(-Tq // q_chunk)
    pad = n_chunks * q_chunk - Tq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    pp = jnp.pad(q_positions, (0, pad), constant_values=-1)
    qs = qp.reshape(q.shape[0], n_chunks, q_chunk, *q.shape[2:])
    ps = pp.reshape(n_chunks, q_chunk)

    def scan_body(_, inp):
        qc, qpos = inp
        return (), block(qc, qpos)

    _, outs = jax.lax.scan(
        scan_body, (), (qs.swapaxes(0, 1), ps)
    )  # outs: (n_chunks, B, q_chunk, H, hd)
    out = outs.swapaxes(0, 1).reshape(q.shape[0], n_chunks * q_chunk, *q.shape[2:])
    return out[:, :Tq]
