"""Seeded weights for a model, made on the device in one jitted call.

The program gets these weights (cast into its own pytree by the traffic
driver) and the plain reference gets the very same arrays, so neither
side's weights come from the program.  The configuration's architecture
file lists the leaves (``harness.architecture``).  Factorized weights
follow FeDLRT's initialisation: orthonormal bases, a diagonal coefficient
with a decaying He-scaled spectrum at full rank; every other leaf is a
constant or drawn by the architecture's own init from the seed.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.chip import harness

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**62 (the driver's seeds
    pass 32 signed bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def orthonormal(key, shape):
    """Orthonormal columns of a Gaussian by CholeskyQR2 (matmuls only)."""
    a = jax.random.normal(key, shape, jnp.float32)
    for _ in range(2):
        g = jnp.einsum("...ni,...nj->...ij", a, a, precision=HIGHEST)
        c = jnp.linalg.cholesky(g)
        eye = jnp.broadcast_to(jnp.eye(shape[-1], dtype=jnp.float32), g.shape)
        c_inv = jax.lax.linalg.triangular_solve(c, eye, left_side=True, lower=True)
        a = jnp.einsum("...ni,...ji->...nj", a, c_inv, precision=HIGHEST)
    return a


def _generate(cfg: dict, arch, key):
    dtype, basis = jnp.dtype(cfg["param_dtype"]), jnp.dtype(cfg["basis_dtype"])
    out = {}
    linears = arch.linears(cfg)
    for i, lin in enumerate(linears):
        ku, kv = jax.random.split(jax.random.fold_in(key, i))
        r = lin.r
        scale = (2.0 * lin.n_out / r) ** 0.5
        sigma = scale * jnp.exp(-jnp.arange(r, dtype=jnp.float32) / r)
        out[lin.path + "/U"] = orthonormal(ku, lin.stack + (lin.n_in, r)).astype(basis)
        out[lin.path + "/V"] = orthonormal(kv, lin.stack + (lin.n_out, r)).astype(basis)
        out[lin.path + "/S"] = jnp.broadcast_to(
            jnp.diag(sigma), lin.stack + (r, r)
        ).astype(dtype)
        out[lin.path + "/rank"] = jnp.full(lin.stack, float(r), jnp.float32)
    # a drawn leaf's key is its place in the list, counted on from the
    # factors': the factors' keys are those of an architecture without it
    for j, (path, shape, init) in enumerate(arch.leaves(cfg)):
        if callable(init):
            out[path] = init(jax.random.fold_in(key, len(linears) + j), shape).astype(dtype)
        else:
            out[path] = jnp.full(shape, init, dtype)
    return out


@functools.lru_cache(maxsize=8)
def _generator(cfg_json: str, root: str):
    cfg = json.loads(cfg_json)
    return jax.jit(functools.partial(_generate, cfg, harness.architecture(cfg, Path(root))))


def generate(cfg: dict, seed: int, root: Path = harness.ROOT) -> dict:
    """``{path: array}`` of every weight of ``cfg``'s architecture (its
    file found under ``root``): bases in the configuration's
    ``basis_dtype``, everything else in its ``param_dtype``."""
    with jax.default_matmul_precision("highest"):
        return _generator(json.dumps(cfg, sort_keys=True), str(root))(seed_key(seed))
