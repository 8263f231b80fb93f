"""Block assembly: attention/Mamba/RWKV mixers + MLP/MoE, scanned stacks.

Layer stacks are organized as *superblocks*: the repeating
``cfg.block_pattern`` is unrolled inside a ``lax.scan`` body whose xs are
the per-position parameter stacks (leading dim = number of superblocks).
This preserves the true layer interleaving (e.g. Jamba's 7:1 mamba:attn)
while keeping the lowered HLO one-superblock-sized — essential for CPU
compile times of the 512-device dry-run.

Serving: the same scan carries the whole cache stacks (leading dim =
superblocks) and each layer writes its new rows into them in place at its
layer index, so decode is a single fused HLO too, and a step moves one row
per sequence where scan xs/ys would copy every layer out and back.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import block_len, decode_attention
from repro.kernels.ops import use_kernels_for
from repro.models import sharding
from repro.models.config import ModelConfig
from repro.models.layers import (
    Builder,
    apply_linear,
    apply_rope,
    attention,
    attention_mask,
    rms_norm,
)
from repro.models.moe import build_moe, moe_block
from repro.models.ssm import (
    build_mamba,
    build_rwkv,
    mamba_init_state,
    mamba_mix,
    rwkv_init_state,
    rwkv_mix,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------


def build_attn(b: Builder, prefix: str, cfg: ModelConfig, n_blocks: int, *, cross: bool = False):
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    bs, ba = (n_blocks,), ("layers",)
    b.linear(f"{prefix}/q", d, H * hd, li="embed", lo="heads",
             batch_shape=bs, batch_axes=ba, bias=cfg.qkv_bias)
    b.linear(f"{prefix}/k", d, Hkv * hd, li="embed", lo="kv_heads",
             batch_shape=bs, batch_axes=ba, bias=cfg.qkv_bias)
    b.linear(f"{prefix}/v", d, Hkv * hd, li="embed", lo="kv_heads",
             batch_shape=bs, batch_axes=ba, bias=cfg.qkv_bias)
    b.linear(f"{prefix}/o", H * hd, d, li="heads", lo="embed",
             batch_shape=bs, batch_axes=ba)
    if cfg.qk_norm:
        b.vector(f"{prefix}/q_norm", bs + (hd,), axes=ba + (None,))
        b.vector(f"{prefix}/k_norm", bs + (hd,), axes=ba + (None,))
    if cross:
        b.linear(f"{prefix}/xq", d, H * hd, li="embed", lo="heads",
                 batch_shape=bs, batch_axes=ba)
        b.linear(f"{prefix}/xk", d, Hkv * hd, li="embed", lo="kv_heads",
                 batch_shape=bs, batch_axes=ba)
        b.linear(f"{prefix}/xv", d, Hkv * hd, li="embed", lo="kv_heads",
                 batch_shape=bs, batch_axes=ba)
        b.linear(f"{prefix}/xo", H * hd, d, li="heads", lo="embed",
                 batch_shape=bs, batch_axes=ba)
        b.vector(f"{prefix}/ln_x", bs + (d,), axes=ba + (None,))


def build_mlp(b: Builder, prefix: str, cfg: ModelConfig, n_blocks: int):
    d, dff = cfg.d_model, cfg.d_ff
    bs, ba = (n_blocks,), ("layers",)
    if cfg.gated_mlp:
        b.linear(f"{prefix}/gate", d, dff, li="embed", lo="ffn",
                 batch_shape=bs, batch_axes=ba)
    b.linear(f"{prefix}/up", d, dff, li="embed", lo="ffn",
             batch_shape=bs, batch_axes=ba)
    b.linear(f"{prefix}/down", dff, d, li="ffn", lo="embed",
             batch_shape=bs, batch_axes=ba)


def build_block(b: Builder, prefix: str, kind: str, cfg: ModelConfig,
                n_blocks: int, *, moe_here: bool, cross: bool = False):
    bs, ba = (n_blocks,), ("layers",)
    b.vector(f"{prefix}/ln1", bs + (cfg.d_model,), axes=ba + (None,))
    b.vector(f"{prefix}/ln2", bs + (cfg.d_model,), axes=ba + (None,))
    if kind == "attn":
        build_attn(b, f"{prefix}/attn", cfg, n_blocks, cross=cross)
    elif kind == "mamba":
        build_mamba(b, f"{prefix}/mamba", cfg, n_blocks)
    elif kind == "rwkv":
        build_rwkv(b, f"{prefix}/rwkv", cfg, n_blocks)
    else:
        raise ValueError(kind)
    if moe_here:
        build_moe(b, f"{prefix}/moe", cfg, n_blocks)
    else:
        build_mlp(b, f"{prefix}/mlp", cfg, n_blocks)


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def attn_mix(
    p: dict,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    cache: Optional[dict],
    layer: Optional[Array] = None,
    causal: bool = True,
    cross_kv: Optional[Tuple[Array, Array]] = None,
    use_rope: bool = True,
):
    """Self-attention (+ optional cached decode, + optional cross-attn block).

    cache: the whole layer stack {"k": (NB,B,Hkv,S,hd), "v": ...,
    "idx": (NB,) int32} or None; this block is layer ``layer`` (a traced
    index) of it.  The new keys and values are written into the stack in
    place and the attention reads layer ``layer`` from it, so a decode
    step moves one row per sequence, not a copy of the layer (one new
    token a sequence: the Pallas ``decode_attention`` wherever the kernel
    policy allows it).  A
    *per-slot* cache carries ``idx`` of shape (NB, B) instead — one write
    position per sequence (continuous batching admits requests into freed
    slots, so rows decode at different depths); ``positions`` is then
    (B, T).  Returns (y, new_cache), new_cache the updated stack.
    """
    B, T, d = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = apply_linear(p["q"], x, bias=p.get("q_b"), kernels=cfg.kernels).reshape(B, T, H, hd)
    k = apply_linear(p["k"], x, bias=p.get("k_b"), kernels=cfg.kernels).reshape(B, T, Hkv, hd)
    v = apply_linear(p["v"], x, bias=p.get("v_b"), kernels=cfg.kernels).reshape(B, T, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # context parallelism: queries stay sequence-sharded; keys/values are
    # gathered (small for GQA) so every shard attends over the full context
    q = sharding.shard(q, "batch", "seq", None, None)

    new_cache = None
    if cache is not None:
        kc, vc = cache["k"], cache["v"]
        S = kc.shape[3]
        if cfg.sliding_window and S < cfg.sliding_window and S < 4096:
            raise ValueError("cache smaller than the attention window")
        # ring/linear write at idx (mod cache length), in place in the
        # layer stack.  NOTE: a multi-token write (prefill) must not wrap:
        # callers size the prefill cache at ≥ prompt length; decode writes
        # are single-token and wrap freely.
        idx = jax.lax.dynamic_index_in_dim(cache["idx"], layer, 0, keepdims=False)
        slot = (idx % S).astype(jnp.int32)
        k, v = k.astype(kc.dtype), v.astype(vc.dtype)
        if idx.ndim:  # per-slot (B,): each row writes at its own position
            # one index per (sequence, kv head, position): each write is a
            # row of hd, so the stack keeps its head-major layout
            rows = jnp.arange(B)[:, None, None]
            heads = jnp.arange(Hkv)[None, :, None]
            cols = (slot[:, None] + jnp.arange(T))[:, None, :]
            ck = kc.at[layer, rows, heads, cols].set(k.swapaxes(1, 2))
            cv = vc.at[layer, rows, heads, cols].set(v.swapaxes(1, 2))
            s_idx = jnp.arange(S, dtype=jnp.int32)[None, :]
            newest = idx.astype(jnp.int32)[:, None] + T - 1  # (B, 1)
        else:
            at = (layer, 0, 0, slot, 0)
            ck = jax.lax.dynamic_update_slice(kc, k.swapaxes(1, 2)[None], at)
            cv = jax.lax.dynamic_update_slice(vc, v.swapaxes(1, 2)[None], at)
            s_idx = jnp.arange(S, dtype=jnp.int32)
            newest = idx.astype(jnp.int32) + T - 1
        # absolute position held by each slot: the newest p ≤ newest-
        # written position with p % S == s; never written → negative.
        kv_pos = newest - ((newest - s_idx) % S)
        kv_pos = jnp.where(kv_pos < 0, jnp.int32(-(10**9)), kv_pos)
        # one new token a sequence: the kernel reads the layer from the
        # stack where it lies; XLA would copy it out before its dot
        use = T == 1 and block_len(S, Hkv, hd, kc.dtype.itemsize) > 0
        use = use and use_kernels_for(cfg.kernels)
        if use:
            mask = attention_mask(positions, kv_pos, causal=causal,
                                  sliding_window=cfg.sliding_window)
            y = decode_attention(
                q[:, 0], jnp.broadcast_to(mask.reshape(-1, S), (B, S)),
                ck, cv, layer, interpret=use == "interpret",
            )[:, None]
        else:
            layer_of = lambda c: jax.lax.dynamic_index_in_dim(
                c, layer, 0, keepdims=False).swapaxes(1, 2)
            y = attention(
                q, layer_of(ck), layer_of(cv),
                q_positions=positions, kv_positions=kv_pos,
                causal=causal, sliding_window=cfg.sliding_window,
                q_chunk=cfg.attn_q_chunk,
            )
        new_cache = {
            "k": ck, "v": cv,
            "idx": jax.lax.dynamic_update_index_in_dim(
                cache["idx"], idx + T, layer, 0
            ),
        }
    else:
        kv_pos = positions
        y = attention(
            q, k, v,
            q_positions=positions, kv_positions=kv_pos,
            causal=causal, sliding_window=cfg.sliding_window,
            q_chunk=cfg.attn_q_chunk,
        )

    y = sharding.shard(y, "batch", "seq", None, None)
    out = apply_linear(p["o"], y.reshape(B, T, H * hd), kernels=cfg.kernels)

    if cross_kv is not None:
        # cross_kv: encoder hidden states (B, Tenc, d)
        xh = rms_norm(x + out, p["ln_x"], cfg.norm_eps)
        qx = apply_linear(p["xq"], xh, kernels=cfg.kernels).reshape(B, T, H, hd)
        Tenc = cross_kv.shape[1]
        ek = apply_linear(p["xk"], cross_kv, kernels=cfg.kernels).reshape(B, Tenc, Hkv, hd)
        ev = apply_linear(p["xv"], cross_kv, kernels=cfg.kernels).reshape(B, Tenc, Hkv, hd)
        yx = attention(
            qx, ek, ev,
            q_positions=positions,
            kv_positions=jnp.arange(Tenc),
            causal=False, sliding_window=0, q_chunk=cfg.attn_q_chunk,
        )
        out = out + apply_linear(p["xo"], yx.reshape(B, T, H * hd), kernels=cfg.kernels)
    return out, new_cache


def mlp_apply(p: dict, x: Array, cfg: ModelConfig) -> Array:
    if cfg.gated_mlp:
        h = jax.nn.silu(
            apply_linear(p["gate"], x, kernels=cfg.kernels)
        ) * apply_linear(p["up"], x, kernels=cfg.kernels)
    else:
        h = jax.nn.gelu(apply_linear(p["up"], x, kernels=cfg.kernels))
    h = sharding.shard(h, "batch", "seq", None)
    return apply_linear(p["down"], h, kernels=cfg.kernels)


def block_apply(
    p: dict,
    kind: str,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    cache: Optional[dict],
    layer: Optional[Array] = None,
    causal: bool = True,
    cross_kv=None,
    use_rope: bool = True,
):
    """One (mixer + FFN/MoE) block with pre-norm residuals.

    ``cache``: the mixer's whole layer stack (leading dim = superblocks),
    of which this block is layer ``layer``.  Returns (x, new_cache,
    aux_loss), new_cache the updated stack."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        mix_out, new_cache = attn_mix(
            p["attn"], h, cfg, positions=positions, cache=cache, layer=layer,
            causal=causal, cross_kv=cross_kv, use_rope=use_rope,
        )
    elif kind in ("mamba", "rwkv"):
        mix = mamba_mix if kind == "mamba" else rwkv_mix
        state = None if cache is None else jax.tree.map(
            lambda s: jax.lax.dynamic_index_in_dim(s, layer, 0, keepdims=False),
            cache,
        )
        mix_out, new_state = mix(p[kind], h, cfg, state=state)
        new_cache = None if cache is None else jax.tree.map(
            lambda s, n: jax.lax.dynamic_update_index_in_dim(s, n, layer, 0),
            cache, new_state,
        )
    else:
        raise ValueError(kind)
    x = x + mix_out
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        ffn_out, aux = moe_block(p["moe"], h2, cfg)
    else:
        ffn_out, aux = mlp_apply(p["mlp"], h2, cfg), jnp.zeros((), jnp.float32)
    return x + ffn_out, new_cache, aux


# ---------------------------------------------------------------------------
# scanned stack over superblocks
# ---------------------------------------------------------------------------


def init_cache_stack(
    cfg: ModelConfig, batch: int, cache_len: int, dtype, *,
    per_slot: bool = False,
) -> dict:
    """Per-position cache stacks (leading dim = superblocks).

    Attention keys and values are head-major, ``(NB, batch, Hkv, S, hd)``:
    each kv head's positions are contiguous, as the decode kernel reads
    them.  ``per_slot=True`` makes the attention write index a vector over the
    batch — (NB, batch) instead of (NB,) — so a serving engine can hold
    sequences at different positions in one decode batch."""
    NB = cfg.superblocks
    Hkv, hd = cfg.num_kv_heads, cfg.hd
    idx_shape = (NB, batch) if per_slot else (NB,)
    caches = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            c = {
                "k": jnp.zeros((NB, batch, Hkv, cache_len, hd), dtype),
                "v": jnp.zeros((NB, batch, Hkv, cache_len, hd), dtype),
                "idx": jnp.zeros(idx_shape, jnp.int32),
            }
        elif kind == "mamba":
            c = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (NB,) + x.shape).copy(),
                mamba_init_state(cfg, batch, dtype),
            )
        elif kind == "rwkv":
            c = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (NB,) + x.shape).copy(),
                rwkv_init_state(cfg, batch, dtype),
            )
        else:
            raise ValueError(kind)
        caches[f"pos{i}"] = c
    return caches


def stack_apply(
    blocks: dict,
    x: Array,
    cfg: ModelConfig,
    *,
    positions: Array,
    caches: Optional[dict] = None,
    causal: bool = True,
    cross_kv=None,
    use_rope: bool = True,
    pattern: Optional[Tuple[str, ...]] = None,
    moe_positions: Optional[Tuple[bool, ...]] = None,
):
    """Scan the superblock stack.  blocks/caches: dicts of stacked params
    and cache stacks.

    The caches are the scan's carry, not its xs/ys: each layer writes its
    new entries into the stacks in place at its layer index, and attention
    reads the layer from them.  ``caches=None`` (training, the encoder)
    scans the blocks alone.  Returns (x, new_caches, total_aux)."""
    pattern = pattern or cfg.block_pattern

    # remat per *layer*, not per superblock: a superblock backward would
    # otherwise hold every member layer's recomputed internals live at once
    # (ruinous for Jamba's 7 Mamba layers per superblock).
    def one_block(kind):
        def f(p_i, h, c_i, layer):
            # pin the layout of the residual stream at every layer: the
            # checkpoint below saves this tensor, and an unpinned save point
            # is replicated (72 × full-T·d f32 on jamba — dozens of GiB)
            h = sharding.shard(h, "batch", "seq", None)
            return block_apply(
                p_i, kind, h, cfg,
                positions=positions, cache=c_i, layer=layer, causal=causal,
                cross_kv=cross_kv, use_rope=use_rope,
            )

        if cfg.remat:
            return jax.checkpoint(f, prevent_cse=False)
        return f

    block_fns = [one_block(kind) for kind in pattern]

    def superblock(h, aux, c_sb, p_sb, layer):
        h = sharding.shard(h, "batch", "seq", None)
        for i in range(len(pattern)):
            key = f"pos{i}"
            c_i = None if c_sb is None else c_sb[key]
            h, nc, a = block_fns[i](p_sb[key], h, c_i, layer)
            if c_sb is not None:
                c_sb = {**c_sb, key: nc}
            aux = aux + a
        return h, aux, c_sb

    carry = (x, jnp.zeros((), jnp.float32))
    if caches is None:
        (h, aux), _ = jax.lax.scan(
            lambda c, p_sb: (superblock(*c, None, p_sb, None)[:2], ()),
            carry, blocks,
        )
        return h, None, aux
    # the cache stacks ride in the carry, indexed by the layer counter:
    # each layer writes its new rows in place (as scan xs/ys, every layer
    # would be sliced out of the stack and written back whole)
    n_blocks = jax.tree.leaves(caches)[0].shape[0]
    (h, aux, new_caches), _ = jax.lax.scan(
        lambda c, xs: (superblock(*c, *xs), ()),
        carry + (caches,), (blocks, jnp.arange(n_blocks)),
    )
    return h, new_caches, aux
