"""Architecture file of a dense decoder whose weights are factorized.

Qwen2 blocks as published: pre-norm RMSNorm, attention with QKV bias,
rotary embeddings on the two halves of each head, causal grouped-query
attention, a SwiGLU MLP, a final RMSNorm and an untied head.  Every
weight matrix is ``W = U S Vᵀ`` and is applied as ``((x U) S) Vᵀ``; the
embedding row of token ``t`` is ``U[t] S Vᵀ``.

It provides what ``harness.py`` lists: the factorized weights, the other
leaves (norm scales and QKV biases, constants), the program's fields this
file must match, the map from the program's paths, the plain reference's
logits and the FLOPs and bytes of a decode step.

Weights arrive as ``{path: array}`` in any float type and are computed
with in float32.  Layers are scanned, so one layer's activations live at
a time.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from benchmarks.chip import counts, layout
from benchmarks.chip.references import arith

F32 = jnp.float32


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------


def linears(cfg: dict) -> List[layout.Linear]:
    """Every factorized weight of the model, embedding and head included."""
    d, dff = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], layout.head_dim(cfg)
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
    return [layout.factorized(cfg, *s) for s in shapes]


def leaves(cfg: dict) -> List[tuple]:
    """(path, shape, init value) of the other leaves: norm scales, QKV bias."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], layout.head_dim(cfg)
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


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


def program_fields(cfg: dict) -> dict:
    """The program's model-config fields that must read as this file says."""
    return {
        "d_model": cfg["hidden_size"], "num_layers": cfg["num_hidden_layers"],
        "num_heads": cfg["num_attention_heads"],
        "num_kv_heads": cfg["num_key_value_heads"], "hd": layout.head_dim(cfg),
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "qkv_bias": cfg["qkv_bias"], "rope_theta": cfg["rope_theta"],
        "norm_eps": cfg["rms_norm_eps"], "tie_embeddings": cfg["tie_word_embeddings"],
        "family": "dense", "block_pattern": ("attn",), "gated_mlp": True,
        "qk_norm": False, "sliding_window": 0, "moe": None,
    }


def program_path(names: tuple) -> str:
    """The program stacks its blocks under the one position of its
    pattern, ``pos0``; the benchmark's paths leave it out."""
    return "/".join(n for n in names if n != "pos0")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


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


def logits(cfg: dict, w: dict, inputs, mode: str = "float32"):
    """Logits (B, T, vocab) for token ids (B, T)."""
    return linear(w, "lm_head", hidden(cfg, w, inputs, mode), mode)


# ---------------------------------------------------------------------------
# the counts
# ---------------------------------------------------------------------------


def decode_step_cost(cfg: dict, contexts: List[int], itemsize: int = 2) -> tuple:
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
    for lin in linears(cfg):
        cost = counts.embed_cost if lin.path == "embed" else counts.factor_cost
        f, b = cost(lin, B, itemsize)
        flops += f
        nbytes += b
    ctx = sum(contexts)
    flops += L * 2 * (2 * hd * H * ctx)
    nbytes += L * itemsize * 2 * Hkv * hd * ctx
    return flops, nbytes
