"""Plain reference of a dense decoder whose weights are factorized.

Qwen2 blocks as published: pre-norm RMSNorm, attention with QKV bias,
rotary embeddings on the two halves of each head, causal grouped-query
attention, a SwiGLU MLP, a final RMSNorm and an untied head.  Every
weight matrix is ``W = U S Vᵀ`` and is applied as ``((x U) S) Vᵀ``; the
embedding row of token ``t`` is ``U[t] S Vᵀ``.

Weights arrive as ``{path: array}`` (see ``benchmarks/chip/layout.py``)
in any float type and are computed with in float32.  Layers are scanned,
so one layer's activations live at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import layout
from benchmarks.chip.references import arith

F32 = jnp.float32


def linear(w: dict, path: str, x, mode: str):
    h = arith.einsum("...i,ir->...r", x, w[path + "/U"], mode)
    h = arith.einsum("...r,rs->...s", h, w[path + "/S"], mode)
    return arith.einsum("...s,os->...o", h, w[path + "/V"], mode)


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, theta: float):
    """Rotary embedding on (B, T, H, hd), positions 0..T-1."""
    hd, T = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg: dict, q, k, v, mode: str):
    """Causal grouped-query attention: q (B,T,H,hd), k/v (B,T,Hkv,hd)."""
    B, T, H, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, H // Hkv, hd)
    s = arith.einsum("btkgd,bskd->bkgts", qg, k, mode) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = arith.einsum("bkgts,bskd->btkgd", p, v, mode)
    return o.reshape(B, T, H * hd)


def block(cfg: dict, p: dict, x, mode: str):
    eps, hd = cfg["rms_norm_eps"], layout.head_dim(cfg)
    B, T, _ = x.shape
    h = rms_norm(x, p["ln1"], eps)
    qkv = []
    for name, heads in (("q", cfg["num_attention_heads"]),
                        ("k", cfg["num_key_value_heads"]),
                        ("v", cfg["num_key_value_heads"])):
        y = linear(p, "attn/" + name, h, mode)
        if cfg["qkv_bias"]:
            y = y + p[f"attn/{name}_b"].astype(F32)
        qkv.append(y.reshape(B, T, heads, hd))
    q, k, v = qkv
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    x = x + linear(p, "attn/o", attention(cfg, q, k, v, mode), mode)
    h = rms_norm(x, p["ln2"], eps)
    gate = linear(p, "mlp/gate", h, mode)
    up = linear(p, "mlp/up", h, mode)
    return x + linear(p, "mlp/down", jax.nn.silu(gate) * up, mode)


def hidden(cfg: dict, w: dict, inputs, mode: str = "float32"):
    """Final-norm hidden states (B, T, d) for token ids (B, T)."""
    u = jnp.take(w["embed/U"], inputs, axis=0)
    x = arith.einsum("btr,rs->bts", u, w["embed/S"], mode)
    x = arith.einsum("bts,ds->btd", x, w["embed/V"], mode)
    blocks = {
        k[len("blocks/"):]: v for k, v in w.items() if k.startswith("blocks/")
    }

    def step(x, p):
        return block(cfg, p, x, mode), None

    x, _ = jax.lax.scan(step, x, blocks)
    return rms_norm(x, w["final_norm"], cfg["rms_norm_eps"])
