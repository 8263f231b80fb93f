"""Substrate tests: data pipeline, partitioners, checkpointing, engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core import FedConfig, init_factor
from repro.data import (
    FederatedBatcher,
    make_classification_data,
    make_token_stream,
    partition_dirichlet,
    partition_iid,
)
from repro.fed import FederatedEngine

from conftest import as_batches, lsq_loss


def test_partition_iid_sizes():
    parts = partition_iid(1000, 7)
    assert len(parts) == 7
    assert all(len(p) == 142 for p in parts)
    allidx = np.concatenate(parts)
    assert len(np.unique(allidx)) == len(allidx)


def test_partition_dirichlet_skew_and_balance():
    x, y = make_classification_data(num_points=2000, num_classes=10, seed=0)
    parts = partition_dirichlet(y, 4, alpha=0.1, seed=0)
    sizes = [len(p) for p in parts]
    assert all(s == 500 for s in sizes)
    # skew: each client's label histogram should be far from uniform
    for p in parts:
        hist = np.bincount(y[p], minlength=10) / len(p)
        assert hist.max() > 0.2  # uniform would be 0.1


def test_batcher_shapes_and_epoch_cycling():
    x = np.arange(100, dtype=np.float32)[:, None]
    parts = partition_iid(100, 4, seed=0)
    b = FederatedBatcher({"x": x}, parts, batch_size=5, steps_per_round=3)
    r = b.next_round()
    assert r["x"].shape == (4, 3, 5, 1)
    # cycle through more than an epoch without error / duplication blowup
    seen = []
    for _ in range(5):
        seen.append(b.next_round()["x"])
    assert np.isfinite(np.stack(seen)).all()


def test_token_stream_is_learnable_markov():
    toks = make_token_stream(vocab_size=64, num_tokens=5000, rank=4, seed=0)
    assert toks.min() >= 0 and toks.max() < 64
    # bigram structure: conditional entropy < unigram entropy
    uni = np.bincount(toks, minlength=64) / len(toks)
    H_uni = -(uni[uni > 0] * np.log(uni[uni > 0])).sum()
    joint = np.zeros((64, 64))
    np.add.at(joint, (toks[:-1], toks[1:]), 1)
    joint /= joint.sum()
    cond = joint / (joint.sum(1, keepdims=True) + 1e-12)
    H_cond = -(joint[joint > 0] * np.log(cond[joint > 0])).sum()
    assert H_cond < H_uni - 0.1


def _dense_table_stream(vocab_size, num_tokens, rank, seed):
    """The stream drawn from the full ``vocab × vocab`` transition table."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((vocab_size, rank)).astype(np.float32)
    B = rng.standard_normal((vocab_size, rank)).astype(np.float32)
    logits = (A @ B.T) / np.sqrt(rank)
    logits -= logits.max(axis=-1, keepdims=True)
    probs = np.exp(logits)
    probs /= probs.sum(axis=-1, keepdims=True)
    cdf = np.cumsum(probs, axis=-1)
    out = np.empty(num_tokens, dtype=np.int32)
    tok = int(rng.integers(vocab_size))
    u = rng.random(num_tokens)
    for i in range(num_tokens):
        tok = min(int(np.searchsorted(cdf[tok], u[i])), vocab_size - 1)
        out[i] = tok
    return out


@pytest.mark.parametrize("vocab,rank", [(64, 4), (512, 16), (8192, 16)])
def test_token_stream_equals_dense_table_chain(vocab, rank):
    want = _dense_table_stream(vocab, 20_000, rank, seed=3)
    got = make_token_stream(vocab_size=vocab, num_tokens=20_000, rank=rank, seed=3)
    np.testing.assert_array_equal(got, want)


def test_token_stream_real_vocab_memory_is_linear():
    """At Qwen2's 152,064 tokens the dense table would be 92 GB of f32."""
    import tracemalloc

    tracemalloc.start()
    try:
        toks = make_token_stream(vocab_size=152_064, num_tokens=300, rank=16)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert toks.min() >= 0 and toks.max() < 152_064
    assert peak < 128 * 2**20, peak


def test_checkpoint_roundtrip(tmp_path, rng_key):
    params = {
        "layer": {
            "w": init_factor(rng_key, 32, 24, r_max=6, init_rank=4),
            "b": jnp.arange(24, dtype=jnp.float32),
        },
        "head": jnp.ones((8, 8)),
    }
    p = str(tmp_path / "ck.npz")
    save_checkpoint(p, params, meta={"round": 7})
    restored, meta = load_checkpoint(p)
    assert meta["round"] == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_allclose(a, b)
    assert float(restored["layer"]["w"].rank) == 4.0


def test_engine_runs_fedlrt_and_checkpoints(tmp_path, homo_prob, rng_key):
    f = init_factor(rng_key, 20, 20, r_max=10, init_rank=10, spectrum_scale=1.0)
    cfg = FedConfig(num_clients=4, s_star=10, lr=0.1, correction="simplified", tau=0.1)
    eng = FederatedEngine(
        lsq_loss, f, cfg, method="fedlrt",
        checkpoint_dir=str(tmp_path), checkpoint_every=5,
    )
    batches = as_batches(homo_prob)

    class StaticBatcher:
        def next_round(self):
            return {k: np.asarray(v) for k, v in batches.items()}

    hist = eng.train(StaticBatcher(), 10, log_every=0)
    assert hist[-1].loss_before < hist[0].loss_before
    assert os.path.exists(tmp_path / "round_000010.npz")
    restored, meta = load_checkpoint(str(tmp_path / "round_000010.npz"))
    assert meta["round"] == 10


def test_engine_method_parity(homo_prob):
    import jax.numpy as jnp

    from conftest import lsq_dense_loss

    cfg = FedConfig(num_clients=4, s_star=10, lr=0.05, tau=0.1)
    batches = as_batches(homo_prob)

    class StaticBatcher:
        def next_round(self):
            return {k: np.asarray(v) for k, v in batches.items()}

    for method in ("fedavg", "fedlin"):
        eng = FederatedEngine(lsq_dense_loss, jnp.zeros((20, 20)), cfg, method=method)
        hist = eng.train(StaticBatcher(), 5, log_every=0)
        assert hist[-1].loss_before < hist[0].loss_before
