"""Matrix products of the plain reference, in a stated precision.

``float32`` is the reference itself: every product at ``HIGHEST`` (on a
TPU a float32 product otherwise runs in bfloat16 passes).  The lower
modes are the controls: each operand of every product is rounded to the
lower type first (``float8`` with a per-tensor scale, as fp8 inference
scales its operands), and the product of the rounded operands is
accumulated in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: a configuration's compute type → the nearest type below it (the control)
LOWER = {"float32": "bfloat16", "bfloat16": "float8"}
MODES = ("float32", "bfloat16", "float8")
_F8_MAX = 448.0  # largest finite float8_e4m3fn


def round_to(x, mode: str):
    """``x`` (float32) as the nearest value of the lower type."""
    if mode == "float32":
        return x
    if mode == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "float8":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, _F8_MAX / amax, 1.0)
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    raise ValueError(f"unknown precision {mode!r}; one of {MODES}")


def _exact(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def einsum(spec: str, a, b, mode: str = "float32"):
    """``jnp.einsum(spec, a, b)`` in float32, its operands in ``mode``."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return _exact(spec, round_to(a, mode), round_to(b, mode))
