"""Pallas kernel validation: shape/dtype sweeps vs the jnp oracles,
executed in interpret mode (kernel bodies run in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import coeff_grad_kernels, lowrank_apply, lowrank_apply_kernels
from repro.kernels import ref
from repro.kernels.coeff_grad import atb
from repro.kernels.lowrank_matmul import avt, xus


def _inputs(M, K, N, R, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    U = (jax.random.normal(ks[1], (K, R)) / np.sqrt(K)).astype(dtype)
    S = jax.random.normal(ks[2], (R, R), dtype)
    V = (jax.random.normal(ks[3], (N, R)) / np.sqrt(N)).astype(dtype)
    return x, U, S, V


# bf16 mantissa = 8 bits; with R=128-term dot products the oracle (f32) and
# kernel (bf16 inputs, f32 accumulate) legitimately differ by ~1e-1 absolute.
TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4), jnp.bfloat16: dict(rtol=5e-2, atol=1.5e-1)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,N,R",
    [
        (8, 16, 8, 8),       # tiny
        (64, 96, 80, 24),    # unaligned rank (pads to 128 lanes)
        (128, 256, 128, 128),  # aligned
        (56, 512, 40, 16),   # M,N not multiples of block
    ],
)
def test_lowrank_forward_sweep(M, K, N, R, dtype):
    x, U, S, V = _inputs(M, K, N, R, dtype)
    y_ref = ref.lowrank_matmul_ref(
        x.astype(jnp.float32), U.astype(jnp.float32),
        S.astype(jnp.float32), V.astype(jnp.float32),
    )
    y = lowrank_apply_kernels(x, U, S, V, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref), **TOL[dtype]
    )


@pytest.mark.parametrize("M,K,N,R", [(32, 48, 40, 16), (64, 128, 64, 32)])
def test_lowrank_custom_vjp_matches_reference(M, K, N, R):
    x, U, S, V = _inputs(M, K, N, R, jnp.float32)
    dy = jax.random.normal(jax.random.PRNGKey(7), (M, N))

    def loss(use_kernels):
        return jax.grad(
            lambda *a: jnp.sum(lowrank_apply(*a, use_kernels) * dy),
            argnums=(0, 1, 2, 3),
        )(x, U, S, V)

    for a, b in zip(loss(False), loss("interpret")):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


def test_compiled_flag_never_falls_back_to_the_interpreter():
    """``use_kernels=True`` is the compiled TPU path only: on the CPU it
    fails loudly instead of quietly running the interpreter."""
    x, U, S, V = _inputs(16, 128, 64, 8, jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        lowrank_apply(x, U, S, V, True)


def test_coeff_grad_projection():
    x, U, S, V = _inputs(64, 96, 80, 24, jnp.float32)
    dy = jax.random.normal(jax.random.PRNGKey(3), (64, 80))
    got = coeff_grad_kernels(x, dy, U, V, interpret=True)
    want = (x @ U).T @ (dy @ V)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bm,bk", [(8, 16), (16, 128), (32, 256)])
def test_xus_tilings(bm, bk):
    x, U, S, _ = _inputs(64, 256, 8, 128, jnp.float32)
    got = xus(x, U, S, bm=bm, bk=bk, interpret=True)
    np.testing.assert_allclose(got, ref.xus_ref(x, U, S), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bm,bn", [(8, 8), (32, 64)])
def test_avt_tilings(bm, bn):
    A = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    V = jax.random.normal(jax.random.PRNGKey(1), (64, 128))
    got = avt(A, V, bm=bm, bn=bn, interpret=True)
    np.testing.assert_allclose(got, ref.avt_ref(A, V), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bm,bka", [(8, 8), (32, 64), (64, 128)])
def test_atb_tilings(bm, bka):
    A = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    B = jax.random.normal(jax.random.PRNGKey(1), (64, 96))
    got = atb(A, B, bm=bm, bka=bka, interpret=True)
    np.testing.assert_allclose(got, ref.atb_ref(A, B), rtol=1e-4, atol=1e-4)


def test_hypothesis_random_shapes():
    """Property-style sweep: random (M,K,N,R) keep kernels == oracle."""
    rng = np.random.default_rng(0)
    for _ in range(6):
        M = int(rng.integers(1, 9)) * 8
        K = int(rng.integers(1, 9)) * 16
        N = int(rng.integers(1, 9)) * 8
        R = int(rng.integers(1, 5)) * 8
        x, U, S, V = _inputs(M, K, N, R, jnp.float32, seed=int(rng.integers(1e6)))
        y = lowrank_apply_kernels(x, U, S, V, interpret=True)
        np.testing.assert_allclose(
            y, ref.lowrank_matmul_ref(x, U, S, V), rtol=1e-4, atol=1e-4
        )
