"""Input ShapeDtypeStructs + shardings for every (arch × input shape).

``input_specs`` returns weak-type-correct, shardable stand-ins — no device
allocation — for each of the four assigned shapes:

  train_4k     seq 4,096   global_batch 256   → FeDLRT train round
  prefill_32k  seq 32,768  global_batch 32    → serve_prefill
  decode_32k   seq 32,768  global_batch 128   → serve_step (1 new token,
                                                 cache of 32k)
  long_500k    seq 524,288 global_batch 1     → serve_step (sub-quadratic
                                                 archs only; see DESIGN.md)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def sanitize_specs(mesh, shapes, specs):
    """Drop sharding on dims the mesh doesn't divide (GSPMD in_shardings
    require exact divisibility — e.g. whisper's vocab 51866 on model=16)."""

    def fix(spec: P, s) -> P:
        dims = s.shape
        out = []
        for i, ax in enumerate(spec):
            if ax is None or i >= len(dims):
                out.append(None if i >= len(dims) else ax)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            out.append(ax if dims[i] % n == 0 else None)
        return P(*out)

    return jax.tree.map(
        fix, specs, shapes, is_leaf=lambda x: isinstance(x, P)
    )


def shape_applies(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """(applies, reason-if-not).  The documented skips of DESIGN.md §4."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "full-attention arch: 500k decode requires sub-quadratic mixer"
    if cfg.is_encdec and shape.name == "long_500k":
        return False, "enc-dec decoder is full-attention (448-token design)"
    return True, ""


def _extra_inputs(cfg: ModelConfig, B: int, batch_axes) -> Dict[str, Any]:
    """Stub-frontend embeddings (the one sanctioned stub)."""
    out: Dict[str, Any] = {}
    if cfg.family == "vlm":
        out["vision_embeds"] = (
            SDS((B, cfg.vision_tokens, cfg.d_model), jnp.float32),
            P(batch_axes, None, None),
        )
    if cfg.family == "audio":
        out["frames"] = (
            SDS((B, cfg.encoder.num_frames, cfg.d_model), jnp.float32),
            P(batch_axes, None, None),
        )
    return out


def _batch_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def train_specs(cfg: ModelConfig, shape: InputShape, num_clients: int, mesh=None):
    """Client-batched LM batch: tokens (C, B, T+1)."""
    assert shape.global_batch % num_clients == 0
    B = shape.global_batch // num_clients
    T = shape.seq_len
    clients = _batch_axes(mesh) if mesh is not None else ("data",)
    batch = {
        "tokens": (SDS((num_clients, B, T + 1), jnp.int32), P(clients, None, None))
    }
    for k, (s, spec) in _extra_inputs(cfg, B, None).items():
        batch[k] = (
            SDS((num_clients,) + s.shape, s.dtype),
            P(clients, *spec[1:] if len(spec) > 1 else ()),
        )
    # text tokens shrink so vision/audio prefix keeps total seq at T
    if cfg.family == "vlm":
        batch["tokens"] = (
            SDS((num_clients, B, T - cfg.vision_tokens + 1), jnp.int32),
            P(clients, None, None),
        )
    structs = {k: v[0] for k, v in batch.items()}
    specs = {k: v[1] for k, v in batch.items()}
    return structs, specs


def prefill_specs(cfg: ModelConfig, shape: InputShape, mesh=None):
    B, T = shape.global_batch, shape.seq_len
    batch_ax = _batch_axes(mesh) if mesh is not None else ("data",)
    items = {"tokens": (SDS((B, T), jnp.int32), P(batch_ax, None))}
    if cfg.family == "vlm":
        items["tokens"] = (
            SDS((B, T - cfg.vision_tokens), jnp.int32), P(batch_ax, None)
        )
    items.update(_extra_inputs(cfg, B, batch_ax))
    structs = {k: v[0] for k, v in items.items()}
    specs = {k: v[1] for k, v in items.items()}
    return structs, specs


def cache_specs(cfg: ModelConfig, model, B: int, cache_len: int, mesh) -> Tuple[Any, Any]:
    """ShapeDtypeStructs + shardings for the decode cache."""
    from repro.launch.mesh import data_axis_size

    structs = jax.eval_shape(lambda: model.init_cache(None, B, cache_len))
    dsize = data_axis_size(mesh)
    batch_ax = _batch_axes(mesh)
    shard_seq = B < dsize  # long_500k: B=1 → shard the cache sequence dim

    msize = mesh.shape["model"]

    def fit(dim: int, axis):
        """Only shard divisible dims (GSPMD in_shardings require it)."""
        if axis is None:
            return None
        n = dsize if axis == batch_ax else msize
        return axis if dim % n == 0 else None

    def spec_for(path, s) -> P:
        name = jax.tree_util.keystr(path)
        nd = len(s.shape)
        bax = None if shard_seq else batch_ax
        if "'k'" in name or "'v'" in name:
            # (NB, B, Hkv, S, hd): prefer kv-head sharding; small-GQA archs
            # (kv < model size) shard head_dim instead; long_500k shards S.
            kv_ax = fit(s.shape[2], "model")
            hd_ax = fit(s.shape[4], "model") if kv_ax is None else None
            seq_ax = batch_ax if shard_seq else None
            return P(None, fit(s.shape[1], bax), kv_ax, seq_ax, hd_ax)
        if "'S'" in name:  # rwkv state (NB, B, H, hd, hd)
            return P(None, fit(s.shape[1], bax), fit(s.shape[2], "model"), None, None)
        if "'h'" in name and nd == 4:  # mamba (NB, B, d_inner, N)
            return P(None, fit(s.shape[1], bax), fit(s.shape[2], "model"), None)
        if "'conv'" in name:  # (NB, B, K-1, d_inner)
            return P(None, fit(s.shape[1], bax), None, fit(s.shape[3], "model"))
        if "'shift'" in name:  # (NB, B, 1, d)
            return P(None, fit(s.shape[1], bax), None, None)
        if "enc_h" in name:  # (B, F, d)
            return P(fit(s.shape[0], bax), None, None)
        return P()  # idx / pos scalars

    specs = jax.tree_util.tree_map_with_path(spec_for, structs)
    return structs, specs


def decode_specs(cfg: ModelConfig, model, shape: InputShape, mesh):
    from repro.launch.mesh import data_axis_size

    B = shape.global_batch
    dsize = data_axis_size(mesh)
    batch_ax = _batch_axes(mesh)
    tok_spec = P(batch_ax, None) if B >= dsize else P(None, None)
    cache_len = shape.seq_len if not cfg.sliding_window else min(
        shape.seq_len, cfg.sliding_window
    )
    cstructs, cspecs = cache_specs(cfg, model, B, cache_len, mesh)
    tokens = SDS((B, 1), jnp.int32)
    return (cstructs, tokens), (cspecs, tok_spec)
