"""Kernel-level benchmark: the low-rank bottleneck chain vs dense matmul.

On this CPU container the Pallas path runs in interpret mode (not timed —
Python emulation), so we time the XLA-compiled reference chain and report
*derived* quantities: FLOPs, HBM bytes, and arithmetic intensity for both
the dense layer and the factorized chain — the compute-side Table-1 claim.

``fused_chain_rows`` exercises the *real* model dispatch path —
``lowrank_apply`` / ``lowrank_apply_nd`` with their custom VJP, (B, T, d)
activations and bf16 sublane padding — timing the compiled custom-VJP
reference against XLA's own autodiff of the chain, and checking interpret-
mode parity of forward and backward on every shape.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import lowrank_apply, lowrank_apply_nd
from repro.kernels import ref


def _timeit(fn, *a, iters=20):
    jax.block_until_ready(fn(*a))  # warm up / compile, fully drained
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*a)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def chain_vs_dense(emit=print):
    M, n, r = 4096, 2048, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (M, n), jnp.float32)
    U = jax.random.normal(ks[1], (n, r)) / np.sqrt(n)
    S = jax.random.normal(ks[2], (r, r))
    V = jax.random.normal(ks[3], (n, r)) / np.sqrt(n)
    W = jax.random.normal(ks[4], (n, n)) / np.sqrt(n)

    lr = jax.jit(lambda *a: ref.lowrank_matmul_ref(*a))
    dn = jax.jit(lambda x, W: x @ W)
    lr(x, U, S, V).block_until_ready()
    dn(x, W).block_until_ready()

    def timeit(fn, *a, iters=20):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*a)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e6

    us_lr = timeit(lr, x, U, S, V)
    us_dn = timeit(dn, x, W)
    flops_lr = 2 * M * (n * r + r * r + r * n)
    flops_dn = 2 * M * n * n
    bytes_lr = 4 * (M * n * 2 + 2 * n * r + r * r)
    bytes_dn = 4 * (M * n * 2 + n * n)
    emit(
        f"kernel_lowrank_chain,{us_lr:.1f},"
        f"flops={flops_lr:.3e};bytes={bytes_lr:.3e};ai={flops_lr/bytes_lr:.1f}"
    )
    emit(
        f"kernel_dense_matmul,{us_dn:.1f},"
        f"flops={flops_dn:.3e};bytes={bytes_dn:.3e};ai={flops_dn/bytes_dn:.1f}"
    )
    emit(
        f"kernel_chain_speedup,{0.0:.1f},"
        f"time_ratio={us_dn/us_lr:.2f};flop_ratio={flops_dn/flops_lr:.2f}"
    )
    # correctness spot check of the pallas interpret path on a small shape
    xs, Us, Ss, Vs = x[:64, :256], U[:256], S, V[:256]
    y_k = lowrank_apply(xs, Us, Ss, Vs, "interpret")
    y_r = ref.lowrank_matmul_ref(xs, Us, Ss, Vs)
    err = float(jnp.abs(y_k - y_r).max())
    emit(f"kernel_pallas_interpret_check,0.0,max_err={err:.2e}")
    out = {"us_lowrank": us_lr, "us_dense": us_dn, "err": err}
    out.update(fused_chain_rows(emit))
    return out


def fused_chain_rows(emit=print):
    """The model's actual dispatch path: custom-VJP fwd+bwd, batched
    activations, bf16 sublane padding — timed on the compiled reference
    branch, parity-checked against the interpret-mode kernel branch."""
    cases = [
        # (label, B, T, K, N, R, dtype) — T chosen so bf16 hits M%16==8
        ("f32_2d", 1, 2048, 1024, 1024, 64, jnp.float32),
        ("f32_btd", 4, 512, 1024, 1024, 64, jnp.float32),
        ("bf16_m8", 1, 1032, 1024, 1024, 64, jnp.bfloat16),
    ]
    results = {}
    for label, B, T, K, N, R, dtype in cases:
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        x = jax.random.normal(ks[0], (B, T, K) if B > 1 else (T, K), dtype)
        U = (jax.random.normal(ks[1], (K, R)) / np.sqrt(K)).astype(dtype)
        S = jax.random.normal(ks[2], (R, R), dtype)
        V = (jax.random.normal(ks[3], (N, R)) / np.sqrt(N)).astype(dtype)

        def fwd_bwd(x, U, S, V, use_kernels):
            def f(*a):
                return jnp.sum(lowrank_apply_nd(*a, use_kernels) ** 2)

            return jax.grad(f, argnums=(0, 1, 2, 3))(x, U, S, V)

        def xla_fwd_bwd(x, U, S, V):
            def f(x, U, S, V):
                h = x.reshape(-1, x.shape[-1])
                return jnp.sum((((h @ U) @ S) @ V.T) ** 2)

            return jax.grad(f, argnums=(0, 1, 2, 3))(x, U, S, V)

        us_vjp = _timeit(jax.jit(lambda *a: fwd_bwd(*a, False)), x, U, S, V)
        us_xla = _timeit(jax.jit(xla_fwd_bwd), x, U, S, V)

        # interpret-mode parity of the fused kernel branch (not timed)
        g_k = fwd_bwd(x, U, S, V, "interpret")
        g_r = fwd_bwd(x, U, S, V, False)
        err = max(
            float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
            for a, b in zip(g_k, g_r)
        )
        emit(
            f"kernel_fused_chain_{label},{us_vjp:.1f},"
            f"xla_autodiff_us={us_xla:.1f};interpret_parity_err={err:.2e}"
        )
        results[label] = {"us_vjp": us_vjp, "us_xla": us_xla, "err": err}
    return results
