"""The KV cache is written in place.

A decode step carries the stacked cache through the layer scan and writes
each layer's new rows into it; the decode attention reads the layer from
the stack (the Pallas kernel, under ``kernels="interpret"`` here, where
the head width allows it).  It must give what the formulation before it
gave: the cache fed through the scan as xs and ys, each layer sliced out
of the stack, its rows written by a per-row ``dynamic_update_slice`` into
that copy, and the layer written back whole.  That formulation is kept
here as the reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.decode_attention as da
from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.models import build_model
from repro.models.config import reduced
from repro.models.layers import (
    apply_embedding,
    apply_linear,
    apply_rope,
    attention,
    attention_mask,
    rms_norm,
)
from repro.models.transformer import block_apply, mlp_apply, stack_apply
from repro.serve.engine import _insert_cache

S = 16  # positions a slot holds: slot 1's writes wrap past it


def _cfg(kernels):
    """Tiny Qwen2 with grouped kv heads; the kernel's case has whole
    128-lane heads, so that the decode attention takes the kernel."""
    extra = dict(head_dim=128) if kernels == "interpret" else {}
    return reduced(get_config("qwen2_7b"), num_kv_heads=2, kernels=kernels, **extra)


def _ref_attn(p, x, cfg, positions, cache):
    """The attention mixer on its own layer's cache (B, Hkv, S, hd)."""
    B, T, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    lin = lambda n, y: apply_linear(p[n], y, bias=p.get(f"{n}_b"), kernels=cfg.kernels)
    q = apply_rope(lin("q", x).reshape(B, T, H, hd), positions, cfg.rope_theta)
    k = apply_rope(lin("k", x).reshape(B, T, Hkv, hd), positions, cfg.rope_theta)
    v = lin("v", x).reshape(B, T, Hkv, hd)
    idx = cache["idx"]
    slot = (idx % S).astype(jnp.int32)
    if idx.ndim:
        write = lambda cb, xb, sb: jax.lax.dynamic_update_slice(
            cb, xb.swapaxes(0, 1), (0, sb, 0))
        ck = jax.vmap(write)(cache["k"], k, slot)
        cv = jax.vmap(write)(cache["v"], v, slot)
        s_idx, newest = jnp.arange(S)[None, :], idx[:, None] + T - 1
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k.swapaxes(1, 2), (0, 0, slot, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.swapaxes(1, 2), (0, 0, slot, 0))
        s_idx, newest = jnp.arange(S), idx + T - 1
    kv_pos = newest - ((newest - s_idx) % S)
    kv_pos = jnp.where(kv_pos < 0, jnp.int32(-(10**9)), kv_pos)
    y = attention(q, ck.swapaxes(1, 2), cv.swapaxes(1, 2),
                  q_positions=positions, kv_positions=kv_pos,
                  causal=True, sliding_window=cfg.sliding_window,
                  q_chunk=cfg.attn_q_chunk)
    out = apply_linear(p["o"], y.reshape(B, T, H * hd), kernels=cfg.kernels)
    return out, {"k": ck, "v": cv, "idx": idx + T}


def _ref_forward(cfg, params, cache, tokens):
    """``serve_step`` / ``serve_prefill`` as they were: the cache as scan
    xs and ys.  Returns (last-token logits, cache)."""
    emb = apply_embedding(params["embed"], tokens, kernels=cfg.kernels)
    positions = cache["pos"][..., None] + jnp.arange(tokens.shape[1])

    def layer(h, xs):
        p, c = xs[0]["pos0"], xs[1]["pos0"]
        a, nc = _ref_attn(p["attn"], rms_norm(h, p["ln1"], cfg.norm_eps), cfg,
                          positions, c)
        h = h + a
        h = h + mlp_apply(p["mlp"], rms_norm(h, p["ln2"], cfg.norm_eps), cfg)
        return h, {"pos0": nc}

    h, stack = jax.lax.scan(layer, emb, (params["blocks"], cache["stack"]))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = apply_linear(params["lm_head"], h[:, -1:], kernels=cfg.kernels)[:, 0]
    return logits, dict(cache, stack=stack, pos=cache["pos"] + tokens.shape[1])


def _close(got, want, exact):
    got, want = np.asarray(got), np.asarray(want)
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("kernels", ["off", "interpret"])
def test_ragged_decode_matches_the_layer_copy_formulation(kernels):
    """Three slots at different depths: two admitted at the start, one
    into the donated state mid-run; slot 1's writes wrap past ``S``.
    Logits agree to 1e-5 of their largest; the caches agree bit for bit
    where the attention is the same jnp code, to 1e-5 under the kernel."""
    cfg = _cfg(kernels)
    exact = kernels == "off"
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    prompts = {0: np.arange(3, 8), 1: np.arange(11, 20), 2: np.arange(30, 33)}
    admit_at = {0: 0, 1: 0, 2: 3}
    insert = jax.jit(_insert_cache, donate_argnums=(0,))
    step = jax.jit(model.serve_step)
    ref_step = jax.jit(lambda p, c, t: _ref_forward(cfg, p, c, t))

    states = {"new": model.init_cache(params, 3, S, per_slot=True),
              "ref": model.init_cache(params, 3, S, per_slot=True)}
    jaxpr = str(jax.make_jaxpr(model.serve_step)(
        params, states["new"], jnp.zeros((3, 1), jnp.int32)))
    assert ("decode_attention" in jaxpr) == (kernels == "interpret")
    last = np.zeros(3, np.int32)
    for t in range(12):
        for slot, at in admit_at.items():
            if at != t:
                continue
            toks = jnp.asarray(prompts[slot], jnp.int32)[None]
            logits, one = model.serve_prefill(params, {"tokens": toks}, cache_len=S)
            ref_logits, ref_one = _ref_forward(cfg, params, model.init_cache(params, 1, S), toks)
            _close(logits, ref_logits, exact)
            jax.tree.map(lambda a, b: _close(a, b, exact), one["stack"], ref_one["stack"])
            for name, c in (("new", one), ("ref", ref_one)):
                old = states[name]
                states[name] = insert(old, c, jnp.int32(slot), jnp.int32(toks.shape[1]))
                assert old["stack"]["pos0"]["k"].is_deleted()
            last[slot] = int(jnp.argmax(logits[0]))
        tokens = jnp.asarray(last)[:, None]
        logits, states["new"] = step(params, states["new"], tokens)
        ref_logits, states["ref"] = ref_step(params, states["ref"], tokens)
        _close(logits, ref_logits, exact)
        jax.tree.map(lambda a, b: _close(a, b, exact), states["new"], states["ref"])
        last = np.array(jnp.argmax(logits, -1), np.int32)
    assert int(states["new"]["pos"][1]) > S  # slot 1 wrapped


def test_the_forward_without_a_cache_is_unchanged():
    """``caches=None`` (training, the encoder) is the plain layer scan,
    bit for bit."""
    cfg = _cfg("off")
    params, _ = build_model(cfg).init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, cfg.d_model))
    positions = jnp.arange(12)

    def layer(carry, p):
        h, aux = carry
        h, _, a = block_apply(p["pos0"], "attn", h, cfg, positions=positions, cache=None)
        return (h, aux + a), ()

    (want, want_aux), _ = jax.lax.scan(
        layer, (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    got, caches, aux = stack_apply(params["blocks"], x, cfg, positions=positions)
    assert caches is None
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(aux), np.asarray(want_aux))


@pytest.mark.parametrize("blocks", [1, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_decode_attention_matches_the_jnp_attention(dtype, tol, blocks, monkeypatch):
    """The kernel (interpret mode) against ``attention`` on the layer's
    slice: grouped heads, ragged masks, the slot read whole or in four
    position blocks (the online softmax).  bfloat16's room is the jnp
    path's own rounding of its scores to bfloat16 before the softmax."""
    NB, B, S, Hkv, G, hd = 3, 4, 512, 2, 3, 128
    itemsize = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(da, "MAX_BLOCK_BYTES", Hkv * (S // blocks) * hd * itemsize)
    assert da.block_len(S, Hkv, hd, itemsize) == S // blocks
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    k = jax.random.normal(ks[0], (NB, B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[1], (NB, B, S, Hkv, hd), dtype)
    q = jax.random.normal(ks[2], (B, 1, Hkv * G, hd), dtype)
    qpos = jnp.asarray([[5], [511], [200], [700]], jnp.int32)
    kvpos = jnp.where(jnp.arange(S) > qpos, -(10**9), jnp.arange(S)).astype(jnp.int32)
    mask = attention_mask(qpos, kvpos, causal=True, sliding_window=0)[:, 0]
    for layer in range(NB):
        want = attention(q, k[layer], v[layer], q_positions=qpos,
                         kv_positions=kvpos, causal=True)[:, 0]
        got = decode_attention(q[:, 0], mask, k.swapaxes(2, 3), v.swapaxes(2, 3),
                               jnp.int32(layer), interpret=True)
        want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
        np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())
