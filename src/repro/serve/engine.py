"""Factor-resident decode engine.

The engine owns the jitted executables and the per-slot decode state; the
scheduler (`repro.serve.scheduler`) owns request admission.  Executable
discipline (the jit-invariant the lint's trace auditor pins):

- **one prefill executable per prompt-length bucket** — prompts are
  right-padded to the next multiple of ``prompt_bucket`` and run at
  ``B = 1``; the causal mask keeps pad keys out of every real query and
  ``last_index`` reads the true last-token logits, so bucketing changes
  compilation count, never tokens;
- **one insert executable** — copies a B=1 prefill cache into slot ``i``
  of the per-slot batch state (slot and true length are traced scalars).
  It *consumes* the state it is given (donated): the slot's rows are
  written in place, and the caller rebinds the state to the result;
- **one decode executable** at the fixed ``(max_batch, cache_len)`` shape —
  every step decodes the full slot array; inactive slots carry garbage
  rows that never escape (the scheduler ignores them).  Inside it the
  cache is written in place, layer by layer; its input state is *not*
  donated, so it stays readable after the step (the benchmark's planted
  fault of a step that returns its input state relies on that), at the
  price of one copy of the cache into the step's output.

On its first call each of the two compiles ahead of time, and puts
``serve.<insert|decode_step>.aliased_bytes`` and ``.temp_bytes`` (its
compiled ``memory_analysis()``: the bytes of its inputs its outputs
reuse, and its scratch) in the hub as gauges and on stderr; the jitted
call reuses that executable.

Params may arrive quantized (`repro.serve.quantize`); dequantization runs
*inside* each executable so only the compressed buffers stay resident.
Every matmul goes through the model trunk's ``apply_linear`` →
``kernels/ops.lowrank_apply`` dispatch: ``U S Vᵀ`` is never materialized
on the factor-resident path.

Sampling is deterministic and batching-invariant: token ``j`` of request
``rid`` draws from ``fold_in(fold_in(key(seed), rid), j)``, so a request's
output is independent of which other requests share the batch (dense
families — exactly the ones ``init_cache(per_slot=True)`` admits).

Spans (the engine's hub; see ``repro.telemetry.hub``): ``serve.prefill``
(attr ``bucket``), ``serve.insert`` and ``serve.decode_step`` cover their
executable's dispatch; ``serve.sample`` (attr ``rows``) covers the
sampler's dispatch, the wait for the device and the copy of the tokens
to the host, so it is where the host waits on the device.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model
from repro.core.factorization import is_factor
from repro.serve.quantize import dequantize_params, is_quantized
from repro.telemetry import get_hub

Array = jax.Array


def _insert_cache(state, one, slot, length):
    """Graft a B=1 prefill cache into row ``slot`` of the per-slot state.

    Name-directed walk: ``idx`` buffers are (NB, batch) write indices,
    ``pos`` is the (batch,) position vector — both stamped to the true
    prompt ``length`` so the right-pad columns beyond it become stale cache
    entries the attention mask already rejects (kv_pos goes negative).
    Every other leaf carries batch on axis 1 under the (NB, ...) stack.
    """
    out = {}
    for k, dv in state.items():
        sv = one[k]
        if isinstance(dv, dict):
            out[k] = _insert_cache(dv, sv, slot, length)
        elif k == "idx":
            out[k] = dv.at[:, slot].set(length)
        elif k == "pos":
            out[k] = dv.at[slot].set(length)
        else:
            out[k] = jax.lax.dynamic_update_index_in_dim(dv, sv[:, 0], slot, 1)
    return out


def decode_matmul_flops(params, *, factor_resident: bool = True) -> float:
    """Per-token decode FLOPs of the pytree's factor leaves (cost-model
    closed forms).

    Only factor leaves are priced: the dense leaves (norms, biases, any
    never-factorized matrices) are identical between the factor-resident
    and materialized paths and cancel in every comparison this function
    feeds.  Embedding factors are priced with ``gather=True`` — their U row
    is gathered, and a *dense* embedding is a pure gather worth 0 FLOPs.
    """
    total = 0.0
    leaves = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: is_factor(x) or is_quantized(x)
    )[0]
    for path, leaf in leaves:
        if not (is_factor(leaf) or is_quantized(leaf)):
            continue
        u = leaf.U if is_factor(leaf) else leaf.u_q
        stack = math.prod(u.shape[:-2])
        gather = any(getattr(k, "key", None) == "embed" for k in path)
        if factor_resident:
            per = cost_model.lowrank_decode_flops(
                leaf.n_in, leaf.n_out, leaf.r_max, gather=gather
            )
        else:
            per = cost_model.dense_decode_flops(
                leaf.n_in, leaf.n_out, gather=gather
            )
        total += stack * per
    return total


class ServeEngine:
    """Jitted decode executables over one prepared (possibly quantized)
    param pytree.  Construct via ``repro.api.experiment.serve(spec)``."""

    def __init__(
        self,
        model,
        params,
        *,
        max_batch: int = 4,
        max_prompt: int = 64,
        prompt_bucket: int = 16,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
        telemetry=None,
    ):
        cfg = model.cfg
        if cfg.is_encdec:
            raise ValueError(
                "the serving engine decodes per-slot; enc-dec (audio) "
                "models need one shared position and are not servable here"
            )
        if max_prompt % prompt_bucket:
            raise ValueError(
                f"prompt_bucket ({prompt_bucket}) must divide "
                f"max_prompt ({max_prompt})"
            )
        self.model = model
        self.params = params
        self.max_batch = int(max_batch)
        self.max_prompt = int(max_prompt)
        self.prompt_bucket = int(prompt_bucket)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.cache_len = self.max_prompt + self.max_new_tokens
        self.hub = telemetry if telemetry is not None else get_hub()

        def step(p, state, tokens):
            return model.serve_step(dequantize_params(p), state, tokens)

        self._step_fn = jax.jit(step)
        self._insert_fn = jax.jit(_insert_cache, donate_argnums=(0,))
        self._compiled = set()  # names whose memory analysis is out
        self._prefill_fns: Dict[int, object] = {}
        self._base_key = jax.random.PRNGKey(self.seed)

        def sample_tokens(logits, rids, steps):
            if self.temperature <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def one(lg, rid, step_i):
                k = jax.random.fold_in(
                    jax.random.fold_in(self._base_key, jnp.maximum(rid, 0)),
                    step_i,
                )
                return jax.random.categorical(k, lg / self.temperature)

            return jax.vmap(one)(logits, rids, steps).astype(jnp.int32)

        self._sample_fn = jax.jit(sample_tokens)

    # ------------------------------------------------------------- state

    def new_state(self):
        """Fresh per-slot decode state at the (max_batch, cache_len) shape."""
        return self.model.init_cache(
            self.params, self.max_batch, self.cache_len, per_slot=True
        )

    # ----------------------------------------------------------- prefill

    def bucket_len(self, length: int) -> int:
        b = self.prompt_bucket
        return -(-length // b) * b

    def prefill(self, prompt):
        """Run one prompt through its length bucket → (logits (1, V), cache).

        Compiles at most ``max_prompt / prompt_bucket`` executables total.
        """
        prompt = np.asarray(prompt, np.int32).ravel()
        length = int(prompt.size)
        if length < 1:
            raise ValueError("empty prompt")
        if length > self.max_prompt:
            raise ValueError(
                f"prompt length {length} exceeds max_prompt={self.max_prompt}"
            )
        lb = self.bucket_len(length)
        fn = self._prefill_fns.get(lb)
        if fn is None:
            cache_len = self.cache_len

            def prefill_fn(p, tokens, last_index):
                return self.model.serve_prefill(
                    dequantize_params(p),
                    {"tokens": tokens},
                    cache_len=cache_len,
                    last_index=last_index,
                )

            fn = jax.jit(prefill_fn)
            self._prefill_fns[lb] = fn
        tokens = np.zeros((1, lb), np.int32)
        tokens[0, :length] = prompt
        with self.hub.span("serve.prefill", bucket=lb):
            return fn(self.params, jnp.asarray(tokens), jnp.int32(length - 1))

    def _call(self, name: str, fn, *args):
        """``fn(*args)``; on the first call, compiled ahead so that its
        memory analysis can be reported (the call reuses the executable)."""
        if name not in self._compiled:
            self._compiled.add(name)
            mem = fn.lower(*args).compile().memory_analysis()
            if mem is not None:
                got = {"aliased_bytes": mem.alias_size_in_bytes,
                       "temp_bytes": mem.temp_size_in_bytes}
                for k, v in got.items():
                    self.hub.gauge(f"{name}.{k}", v)
                print(f"{name}: compiled, "
                      + ", ".join(f"{k} {v}" for k, v in got.items()),
                      file=sys.stderr, flush=True)
        return fn(*args)

    def insert(self, state, cache, slot: int, length: int):
        """Graft a B=1 prefill ``cache`` into ``state`` row ``slot``.

        Consumes ``state``: its buffers are donated to the result, which
        the caller must use in its place."""
        with self.hub.span("serve.insert"):
            return self._call(
                "serve.insert", self._insert_fn,
                state, cache, jnp.int32(slot), jnp.int32(length),
            )

    # ------------------------------------------------------------ decode

    def step(self, state, last_tokens):
        """One decode step over all slots: (B,) tokens → (logits, state).
        ``state`` stays readable: the step does not consume it."""
        with self.hub.span("serve.decode_step"):
            tokens = jnp.asarray(last_tokens, jnp.int32).reshape(
                self.max_batch, 1
            )
            return self._call(
                "serve.decode_step", self._step_fn, self.params, state, tokens
            )

    def sample(self, logits, rids, steps) -> np.ndarray:
        """Batching-invariant sampling: greedy at temperature 0, else a
        categorical draw keyed on (seed, rid, token index).  Returns once
        the tokens are on the host."""
        with self.hub.span("serve.sample", rows=len(rids)):
            out = self._sample_fn(
                jnp.asarray(logits),
                jnp.asarray(rids, jnp.int32),
                jnp.asarray(steps, jnp.int32),
            )
            return np.asarray(out)

    # ----------------------------------------------------------- costing

    def decode_flops_per_token(self) -> Optional[float]:
        """Factor-leaf decode FLOPs per token per sequence (cost model);
        ``None`` for a materialized pytree — once densified, the ex-factor
        leaves are indistinguishable from always-dense ones, so price the
        dense path via ``decode_matmul_flops(factor_params,
        factor_resident=False)`` on the *source* pytree instead."""
        has_factors = any(
            is_factor(x) or is_quantized(x)
            for x in jax.tree.leaves(
                self.params, is_leaf=lambda x: is_factor(x) or is_quantized(x)
            )
        )
        if not has_factors:
            return None
        return decode_matmul_flops(self.params, factor_resident=True)

    def num_executables(self) -> int:
        """Live compiled-executable count (prefill buckets + insert + step)."""
        return len(self._prefill_fns) + 2
