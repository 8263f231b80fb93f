"""jit-ready wrappers around the Pallas kernels with a custom VJP.

``lowrank_apply(x, U, S, V)`` computes the forward chain with the fused
kernels and wires the backward pass through the same primitives:

    A  = (x U) S                     [xus kernel]
    y  = A Vᵀ                        [avt kernel]
    dA = dy V                        [avt-with-swap ≡ matmul vs V]
    dx = (dA Sᵀ) Uᵀ                  [xus(dy·V, Sᵀ)→ then avt vs U]
    dU = xᵀ (dy V Sᵀ)                [atb kernel]
    dS = (x U)ᵀ (dy V)               [atb kernel — the Ũᵀ(·)Ṽ projection]
    dV = dyᵀ (x U S)                 [atb kernel]

``use_kernels=True`` means the compiled TPU kernels and nothing else: on
a backend that is not a TPU the lowering fails instead of quietly running
something slower.  The Pallas interpreter (kernel bodies executed in
Python, for validation on CPU) is reached only by ``"interpret"``.

Padding rules (all exact — padded rows/columns are zero):
- the rank dim is padded to a multiple of 128 lanes;
- every other dim is padded up to a multiple of its tile size instead of
  shrinking the tile to a divisor — a prime M costs at most one extra tile
  of zeros, never a degenerate 1-wide grid;
- tile sublanes are dtype-aware: (8, 128) for f32 but (16, 128) for bf16,
  so a bf16 input with ``M % 16 == 8`` pads to the next multiple of 16
  rather than handing the MXU a misaligned tile.

``lowrank_apply_nd`` generalizes to leading activation batch dims
((B, T, d) is flattened to 2D) and stacked factors (leading layer/expert
axes on U/S/V are vmapped — the :class:`LowRankFactor` buffer layout used
by scanned layer stacks and MoE experts).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.coeff_grad import atb
from repro.kernels.constraints import LANE
from repro.kernels.lowrank_matmul import _min_sublane as _sublane
from repro.kernels.lowrank_matmul import avt, xus

#: model-level kernel dispatch policies (ModelConfig.kernels / --kernels)
KERNEL_POLICIES = ("auto", "interpret", "off")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_kernels_for(policy: str):
    """Resolve a kernel policy string to the ``lowrank_apply`` flag.

    - ``"auto"``: Pallas kernels on TPU *without an active GSPMD mesh*,
      jnp reference elsewhere → ``True`` / ``False``.  ``pl.pallas_call``
      has no SPMD partitioning rule, so under a mesh the compiled kernels
      would force all-gathers of the sharded activations/factors; the
      reference chain (which GSPMD partitions fine) is the fast path
      there until the kernels grow a shard_map wrapper.
    - ``"interpret"``: force the kernel path through the Pallas interpreter
      on **any** backend — including TPU, where it overrides the compiled
      path for interpreter-based validation → ``"interpret"``.
    - ``"off"``: plain jnp chain → ``False``.
    """
    if policy not in KERNEL_POLICIES:
        raise ValueError(
            f"kernels policy must be one of {KERNEL_POLICIES}, got {policy!r}"
        )
    if policy == "interpret":
        flag = "interpret"
    elif policy != "auto" or not on_tpu():
        flag = False
    else:
        from repro.utils import meshctx

        flag = meshctx.mesh() is None
    # lazy import: this module must stay importable (and statically
    # interpretable by the RPL009 shape checker) without the hub machinery
    from repro.telemetry import get_hub

    get_hub().counter("kernels.dispatch", policy=policy, resolved=str(flag))
    return flag


# ---------------------------------------------------------------------------
# dtype-aware tile padding
# ---------------------------------------------------------------------------


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block(pref: int, size: int, mult: int) -> int:
    """Tile size for a dim that will be zero-padded to a multiple of
    ``mult``: the preferred block when the (padded) dim exceeds it, else
    the whole padded dim.  Never degrades below ``mult`` — prime dims are
    padded, not shrunk to 1-wide grids."""
    assert pref % mult == 0, (pref, mult)
    padded = _round_up(size, mult)
    return pref if padded >= pref else padded


def _pad2(x, rows: int, cols: int):
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return jnp.pad(x, ((0, pr), (0, pc))) if pr or pc else x


def _pad_rank(U, S, V):
    R = U.shape[1]
    Rp = _round_up(R, LANE)
    if Rp == R:
        return U, S, V
    pu = ((0, 0), (0, Rp - R))
    return (
        jnp.pad(U, pu),
        jnp.pad(S, ((0, Rp - R), (0, Rp - R))),
        jnp.pad(V, pu),
    )


# ---------------------------------------------------------------------------
# shape-safe kernel wrappers (arbitrary M/K/N; rank dims already LANE-padded)
# ---------------------------------------------------------------------------


def _xus(x, U, S, *, interpret: bool):
    """A = (x U) S for arbitrary (M, K); tiles aligned per x's dtype."""
    M, K = x.shape
    bm = _block(256, M, _sublane(x.dtype))
    bk = _block(512, K, LANE)
    x2 = _pad2(x, _round_up(M, bm), _round_up(K, bk))
    U2 = _pad2(U, _round_up(K, bk), U.shape[1])
    return xus(x2, U2, S, bm=bm, bk=bk, interpret=interpret)[:M]


def _avt(A, V, *, interpret: bool):
    """y = A Vᵀ for arbitrary (M, N)."""
    M = A.shape[0]
    N = V.shape[0]
    bm = _block(256, M, _sublane(A.dtype))
    bn = _block(256, N, LANE)
    A2 = _pad2(A, _round_up(M, bm), A.shape[1])
    V2 = _pad2(V, _round_up(N, bn), V.shape[1])
    return avt(A2, V2, bm=bm, bn=bn, interpret=interpret)[:M, :N]


def _atb(A, B, *, interpret: bool):
    """C = Aᵀ B for arbitrary (M, Ka); zero rows are exact under the M
    reduction.  Kb (= the rank dim) must already be LANE-padded."""
    M, Ka = A.shape
    bm = _block(512, M, _sublane(A.dtype))
    bka = _block(256, Ka, LANE)
    A2 = _pad2(A, _round_up(M, bm), _round_up(Ka, bka))
    B2 = _pad2(B, _round_up(M, bm), B.shape[1])
    return atb(A2, B2, bm=bm, bka=bka, interpret=interpret)[:Ka]


def lowrank_apply_kernels(x, U, S, V, *, interpret: bool) -> jax.Array:
    """Forward chain through the Pallas kernels (padded + tiled)."""
    U, S, V = _pad_rank(U, S, V)
    A = _xus(x, U, S, interpret=interpret)
    return _avt(A, V, interpret=interpret)


def coeff_grad_kernels(x, dy, U, V, *, interpret: bool) -> jax.Array:
    """∇_S L = (x U)ᵀ (dy V) via the atb kernel (paper's client backward)."""
    R = U.shape[1]
    U2, _, V2 = _pad_rank(U, jnp.zeros((R, R), U.dtype), V)
    eye = jnp.eye(U2.shape[1], dtype=jnp.float32)
    A = _xus(x, U2, eye, interpret=interpret)
    B = _xus(dy, V2, eye, interpret=interpret)
    return _atb(A, B, interpret=interpret)[:R, :R]


# ---------------------------------------------------------------------------
# custom-VJP entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def lowrank_apply(x, U, S, V, use_kernels=False):
    """y = ((x U) S) Vᵀ with a kernel-backed custom VJP.

    ``use_kernels``: ``True`` → the compiled Pallas kernels (TPU only);
    ``"interpret"`` → the Pallas path through the interpreter on *every*
    backend; ``False`` → pure-jnp reference (XLA fuses well on its own for
    small sizes).
    """
    if use_kernels:
        return lowrank_apply_kernels(
            x, U, S, V, interpret=use_kernels == "interpret"
        )
    return ref.lowrank_matmul_ref(x, U, S, V)


def _fwd(x, U, S, V, use_kernels):
    y = lowrank_apply(x, U, S, V, use_kernels)
    return y, (x, U, S, V)


def _bwd(use_kernels, resids, dy):
    x, U, S, V = resids
    interpret = use_kernels == "interpret"

    if use_kernels:
        U_, S_, V_ = _pad_rank(U, S, V)
        eye = jnp.eye(U_.shape[1], dtype=jnp.float32)
        dyV = _xus(dy, V_, eye, interpret=interpret)
        xU = _xus(x, U_, eye, interpret=interpret)
        dA = _xus(dy, V_, jnp.transpose(S_).astype(jnp.float32),
                  interpret=interpret)  # dy V Sᵀ
        dx = _avt(dA, U_, interpret=interpret)
        dU = _atb(x, dA, interpret=interpret)
        dS = _atb(xU, dyV, interpret=interpret)
        xUS = _xus(x, U_, S_.astype(jnp.float32), interpret=interpret)
        dV = _atb(dy, xUS, interpret=interpret)
        R = U.shape[1]
        return (
            dx.astype(x.dtype),
            dU[:, :R].astype(U.dtype),
            dS[:R, :R].astype(S.dtype),
            dV[:, :R].astype(V.dtype),
        )

    dyV = dy @ V
    xU = x @ U
    dx = (dyV @ S.T) @ U.T
    dU = x.T @ (dyV @ S.T)
    dS = xU.T @ dyV
    dV = dy.T @ (xU @ S)
    return (
        dx.astype(x.dtype),
        dU.astype(U.dtype),
        dS.astype(S.dtype),
        dV.astype(V.dtype),
    )


lowrank_apply.defvjp(_fwd, _bwd)


def lowrank_apply_nd(x, U, S, V, use_kernels=False) -> jax.Array:
    """:func:`lowrank_apply` for the shapes model code actually has.

    - ``x`` may carry leading batch dims (``(B, T, d)`` activations): they
      are flattened into the kernel's M dim and restored on the output.
    - ``U/S/V`` may carry leading stack dims (scanned layer stacks, MoE
      experts — the batched :class:`LowRankFactor` buffer layout): the
      apply is vmapped over the stack axis, matching ``x``'s leading axes.
    """
    if U.ndim > 2:
        return jax.vmap(lowrank_apply_nd, in_axes=(0, 0, 0, 0, None))(
            x, U, S, V, use_kernels
        )
    if x.ndim == 2:
        return lowrank_apply(x, U, S, V, use_kernels)
    lead = x.shape[:-1]
    y = lowrank_apply(x.reshape(-1, x.shape[-1]), U, S, V, use_kernels)
    return y.reshape(lead + (V.shape[0],))
