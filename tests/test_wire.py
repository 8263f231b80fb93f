"""The wire layer: codecs, measured accounting, and the identity pin.

Covers the wire-layer contract from three sides:

- codec algebra: round-trip exactness (identity / topk_rank), bounded
  error (downcast / int8_affine), and byte accounting per codec;
- the round data plane: ``wire=identity`` must be bit-identical to the
  undecorated path for every method, and its measured bytes must equal the
  analytic :func:`repro.core.cost_model.wire_round_bytes` exactly;
- the engine: measured ``comm_total_bytes`` vs analytic
  ``comm_total_bytes_analytic``, and the int8 uplink-compression headline
  (≥ 3× measured uplink reduction on the fig5-style MLP head).

Plus the FedConfig validation error paths (they guard the same API).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FedConfig,
    fedavg_round,
    fedlin_round,
    fedlrt_naive_round,
    fedlrt_round,
    init_factor,
    lr_matmul,
)
from repro.core import cost_model
from repro.data import FederatedBatcher, make_classification_data, partition_iid
from repro.fed import FederatedEngine
from repro.fed.wire import (
    DowncastCodec,
    IdentityCodec,
    Int8AffineCodec,
    Payload,
    TopKRankCodec,
    Wire,
    make_codec,
    payload_nbytes,
)

from conftest import as_batches, lsq_dense_loss, lsq_loss


# ---------------------------------------------------------------------------
# codec algebra
# ---------------------------------------------------------------------------


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert jnp.asarray(x).dtype == jnp.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _demo_tree(key, big=(96, 48), small=(7,)):
    k1, k2 = jax.random.split(key)
    return {
        "w": 3.0 * jax.random.normal(k1, big),
        "b": jax.random.normal(k2, small),
        "n": jnp.int32(4),
    }


def test_make_codec_specs():
    assert isinstance(make_codec("identity"), IdentityCodec)
    assert isinstance(make_codec("int8_affine"), Int8AffineCodec)
    assert isinstance(make_codec("topk_rank"), TopKRankCodec)
    dc = make_codec("downcast:float16")
    assert isinstance(dc, DowncastCodec) and dc.wire_dtype == jnp.float16
    assert make_codec("downcast").wire_dtype == jnp.bfloat16
    codec = IdentityCodec()
    assert make_codec(codec) is codec  # built codecs pass through
    with pytest.raises(ValueError, match="unknown wire codec"):
        make_codec("gzip")
    with pytest.raises(ValueError, match="takes no argument"):
        make_codec("int8_affine:7")


def test_identity_roundtrip_and_bytes():
    tree = _demo_tree(jax.random.PRNGKey(0))
    codec = IdentityCodec()
    msg = codec.encode(Payload(tensors=tree))
    _tree_equal(codec.decode(msg).tensors, tree)
    assert codec.nbytes(msg) == payload_nbytes(tree) == 96 * 48 * 4 + 7 * 4 + 4


def test_downcast_roundtrip_within_eps_and_halves_bytes():
    tree = _demo_tree(jax.random.PRNGKey(1))
    codec = DowncastCodec()
    msg = codec.encode(Payload(tensors=tree))
    dec = codec.decode(msg).tensors
    # large float tensor: bf16 on the wire (relative error ≤ 2^-8),
    # restored to f32 at rest
    assert dec["w"].dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(dec["w"]), np.asarray(tree["w"]), rtol=2.0 ** -8, atol=1e-6
    )
    # small / integer leaves travel verbatim
    np.testing.assert_array_equal(np.asarray(dec["b"]), np.asarray(tree["b"]))
    assert int(dec["n"]) == 4 and dec["n"].dtype == jnp.int32
    assert codec.nbytes(msg) == 96 * 48 * 2 + 7 * 4 + 4


def test_int8_affine_error_bounded_by_half_scale():
    tree = _demo_tree(jax.random.PRNGKey(2))
    codec = Int8AffineCodec()
    msg = codec.encode(Payload(tensors=tree))
    dec = codec.decode(msg).tensors
    w = np.asarray(tree["w"])
    scale = (w.max() - w.min()) / 255.0
    err = np.abs(np.asarray(dec["w"]) - w)
    assert err.max() <= scale / 2 + 1e-5
    np.testing.assert_array_equal(np.asarray(dec["b"]), np.asarray(tree["b"]))
    # int8 payload + 8B (lo, scale) for the one compressed tensor
    assert codec.nbytes(msg) == 96 * 48 + 8 + 7 * 4 + 4


def test_int8_affine_batched_keeps_per_client_scales():
    """A (C, …) payload quantizes per client slice: one client's outlier
    must not widen another client's quantization step."""
    x = jnp.concatenate(
        [jnp.ones((1, 16, 16)), 1e3 * jnp.ones((1, 16, 16))], axis=0
    ) + 0.01 * jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16))
    codec = Int8AffineCodec()
    dec = codec.decode(codec.encode(Payload(tensors=x, batched=True))).tensors
    err = np.abs(np.asarray(dec) - np.asarray(x))
    # per-slice scale: client 0's range is ~0.1, so its error stays tiny
    # even though client 1's values are 1000× larger
    assert err[0].max() < 1e-3
    assert err[1].max() <= (np.ptp(np.asarray(x[1])) / 255.0) / 2 + 1e-4


def test_topk_rank_exact_and_bytes_track_rank():
    full = init_factor(jax.random.PRNGKey(4), 40, 30, r_max=8, init_rank=8)
    codec = TopKRankCodec()
    msg_full = codec.encode(Payload(tensors={"w": full}))
    _tree_equal(codec.decode(msg_full).tensors, {"w": full})
    # at full rank the effective slice is the whole buffer: identity bytes
    assert float(codec.nbytes(msg_full)) == payload_nbytes({"w": full})
    # a truncated factor (invariant: inactive columns zero) costs less and
    # still round-trips exactly
    m = (jnp.arange(8) < 3).astype(jnp.float32)
    low = dataclasses.replace(
        full, U=full.U * m, V=full.V * m,
        S=full.S * m[:, None] * m[None, :], rank=jnp.float32(3.0),
    )
    msg_low = codec.encode(Payload(tensors={"w": low}))
    _tree_equal(codec.decode(msg_low).tensors, {"w": low})
    expect = ((40 + 30) * 3 + 3 * 3) * 4 + 4  # leading-σ slice + rank counter
    assert float(codec.nbytes(msg_low)) == expect
    assert float(codec.nbytes(msg_low)) < float(codec.nbytes(msg_full))


# ---------------------------------------------------------------------------
# the round data plane
# ---------------------------------------------------------------------------


@pytest.fixture()
def cfg():
    return FedConfig(
        num_clients=4, s_star=3, lr=0.05, correction="simplified", tau=0.05
    )


def _factor_params(key=0):
    f = init_factor(jax.random.PRNGKey(key), 12, 12, r_max=4, init_rank=4)
    return {"w1": f, "b": jnp.zeros((12,))}


def _factor_loss(p, batch):
    return jnp.mean((lr_matmul(batch["x"], p["w1"]) + p["b"] - batch["y"]) ** 2)


def _batch(C=4):
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    return {
        "x": jax.random.normal(ks[0], (C, 16, 12)),
        "y": jax.random.normal(ks[1], (C, 16, 12)),
    }


@pytest.mark.parametrize("correction", ["none", "simplified", "full"])
def test_fedlrt_identity_wire_bit_identical(correction, cfg):
    """The satellite pin: an identity-codec wire must not change a single
    bit of a fedlrt round, for every correction mode."""
    cfg = dataclasses.replace(cfg, correction=correction)
    params, batch = _factor_params(), _batch()
    p_a, m_a = fedlrt_round(_factor_loss, params, batch, cfg)
    p_b, m_b = fedlrt_round(
        _factor_loss, params, batch, cfg, wire=Wire("identity")
    )
    _tree_equal(p_a, p_b)
    np.testing.assert_array_equal(
        np.asarray(m_a["loss_after"]), np.asarray(m_b["loss_after"])
    )


def test_topk_rank_wire_bit_identical(cfg):
    """topk_rank is lossless by the zero-inactive-columns invariant."""
    params, batch = _factor_params(), _batch()
    p_a, _ = fedlrt_round(_factor_loss, params, batch, cfg)
    p_b, m_b = fedlrt_round(
        _factor_loss, params, batch, cfg, wire=Wire("topk_rank")
    )
    _tree_equal(p_a, p_b)
    assert m_b["wire_bytes_down_per_client"] > 0


def test_dense_identity_wire_bit_identical(homo_prob, cfg):
    batches = as_batches(homo_prob)
    W0 = jnp.zeros((20, 20))
    for round_fn in (fedavg_round, fedlin_round):
        p_a, _ = round_fn(lsq_dense_loss, W0, batches, cfg)
        p_b, _ = round_fn(lsq_dense_loss, W0, batches, cfg, wire=Wire("identity"))
        _tree_equal(p_a, p_b)


def test_measured_identity_bytes_match_analytic_exactly(cfg):
    """Acceptance pin: measured per-round bytes == cost_model analytic
    bytes for the identity codec, per direction, per method."""
    params, batch = _factor_params(), _batch()
    for correction in ("none", "simplified", "full"):
        cfg_c = dataclasses.replace(cfg, correction=correction)
        _, m = fedlrt_round(
            _factor_loss, params, batch, cfg_c, wire=Wire("identity")
        )
        ana = cost_model.wire_round_bytes(params, "fedlrt", correction=correction)
        assert float(m["wire_bytes_down_per_client"]) == ana["down"]
        assert float(m["wire_bytes_up_per_client"]) == ana["up"]


def test_measured_identity_bytes_match_analytic_dense_and_naive(homo_prob, cfg):
    batches = as_batches(homo_prob)
    W0 = {"w": jnp.zeros((20, 20)), "b": jnp.zeros((20,))}

    def dense_loss(p, b):
        return lsq_dense_loss(p["w"] + p["b"][:, None] * 0.0, b)

    for name, fn in (("fedavg", fedavg_round), ("fedlin", fedlin_round)):
        _, m = fn(dense_loss, W0, batches, cfg, wire=Wire("identity"))
        ana = cost_model.wire_round_bytes(W0, name)
        assert float(m["wire_bytes_down_per_client"]) == ana["down"]
        assert float(m["wire_bytes_up_per_client"]) == ana["up"]

    f = init_factor(jax.random.PRNGKey(0), 20, 20, r_max=10, init_rank=10)
    _, m = fedlrt_naive_round(lsq_loss, f, batches, cfg, wire=Wire("identity"))
    ana = cost_model.wire_round_bytes(f, "fedlrt_naive")
    assert float(m["wire_bytes_down_per_client"]) == ana["down"]
    assert float(m["wire_bytes_up_per_client"]) == ana["up"]


def test_lossy_wire_round_stays_finite(cfg):
    params, batch = _factor_params(), _batch()
    for codec in ("downcast", "int8_affine"):
        p, m = fedlrt_round(_factor_loss, params, batch, cfg, wire=Wire(codec))
        assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(p))
        assert float(m["wire_bytes_down_per_client"]) < float(
            cost_model.wire_round_bytes(params, "fedlrt")["down"]
        )


# ---------------------------------------------------------------------------
# engine accounting + the compression headline
# ---------------------------------------------------------------------------

DIM, NCLS, HID = 32, 4, 128


def _mlp_params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {
        "w1": init_factor(k1, DIM, HID, r_max=12, init_rank=12),
        "b1": jnp.zeros((HID,)),
        "w2": 0.06 * jax.random.normal(k2, (HID, NCLS)),
        "b2": jnp.zeros((NCLS,)),
    }


def _mlp_loss(p, batch):
    h = jax.nn.relu(lr_matmul(batch["x"], p["w1"]) + p["b1"])
    logp = jax.nn.log_softmax(h @ p["w2"] + p["b2"])
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], -1))


def _mlp_engine(wire_codec, rounds=4, C=4):
    x, y = make_classification_data(
        dim=DIM, num_classes=NCLS, rank=4, num_points=1024, noise=0.2, seed=0
    )
    parts = partition_iid(len(x), C, seed=0)
    batcher = FederatedBatcher({"x": x, "y": y}, parts, batch_size=32, seed=0)
    cfg = FedConfig(
        num_clients=C, s_star=4, lr=5e-2, tau=0.03, correction="simplified",
        eval_after=True,
    )
    eng = FederatedEngine(
        _mlp_loss, _mlp_params(), cfg, method="fedlrt",
        wire_codec=wire_codec, donate=False,
    )
    hist = eng.train(batcher, rounds, log_every=0)
    return eng, hist


def test_engine_measured_vs_analytic_accounting():
    eng, hist = _mlp_engine("identity", rounds=3)
    assert all(r.wire_codec == "identity" for r in hist)
    assert all(
        r.wire_bytes_down_per_client > 0 and r.wire_bytes_up_per_client > 0
        for r in hist
    )
    measured = sum(
        (r.wire_bytes_down_per_client + r.wire_bytes_up_per_client)
        * r.cohort_size
        for r in hist
    )
    assert eng.comm_total_bytes() == pytest.approx(measured)
    # the analytic (paper-protocol) figure is preserved, and differs: it
    # prices the multi-message protocol, not the phase-boundary payloads
    assert eng.comm_total_bytes_analytic() == pytest.approx(
        sum(r.comm_bytes_per_client * r.cohort_size for r in hist)
    )
    assert eng.comm_total_bytes() != eng.comm_total_bytes_analytic()


def test_engine_wire_none_falls_back_to_analytic():
    eng, hist = _mlp_engine(None, rounds=2)
    assert all(r.wire_codec == "" for r in hist)
    assert eng.comm_total_bytes() == pytest.approx(eng.comm_total_bytes_analytic())


def test_comm_total_bytes_mixed_history():
    """The documented best-effort contract for *mixed* histories: metered
    rounds contribute their measured bytes, while unmetered rounds —
    restored from a pre-wire checkpoint, or run with ``wire_codec=None``
    — contribute the analytic ``comm_bytes_per_client`` instead.  The
    analytic total stays uniform across all three."""
    from repro.fed import RoundResult

    eng, _ = _mlp_engine(None, rounds=0)
    pre_wire = RoundResult(  # restored from a pre-wire checkpoint: no
        round_idx=0,         # wire fields at all beyond their defaults
        loss_before=1.0, loss_after=None,
        comm_bytes_per_client=100.0, ranks={}, seconds=0.0, cohort_size=2,
    )
    metered = RoundResult(
        round_idx=1, loss_before=0.9, loss_after=None,
        comm_bytes_per_client=999.0,  # analytic — must NOT enter the total
        ranks={}, seconds=0.0, cohort_size=3,
        wire_bytes_down_per_client=30.0, wire_bytes_up_per_client=20.0,
        wire_codec="identity",
    )
    unmetered = RoundResult(  # wire_codec=None round: raw pytrees
        round_idx=2, loss_before=0.8, loss_after=None,
        comm_bytes_per_client=50.0, ranks={}, seconds=0.0, cohort_size=4,
    )
    eng.history = [pre_wire, metered, unmetered]
    assert eng.comm_total_bytes() == pytest.approx(
        100.0 * 2 + (30.0 + 20.0) * 3 + 50.0 * 4
    )
    assert eng.comm_total_bytes_analytic() == pytest.approx(
        100.0 * 2 + 999.0 * 3 + 50.0 * 4
    )


def test_int8_uplink_compression_headline():
    """≥ 3× measured uplink byte reduction vs identity, with the round
    still training (the full accuracy-delta sweep lives in bench_wire)."""
    eng_id, hist_id = _mlp_engine("identity", rounds=4)
    eng_q, hist_q = _mlp_engine("int8_affine", rounds=4)
    up_id = sum(r.wire_bytes_up_per_client for r in hist_id)
    up_q = sum(r.wire_bytes_up_per_client for r in hist_q)
    assert up_id / up_q >= 3.0
    # quantization noise must not derail training on this easy task
    assert hist_q[-1].loss_after < hist_q[0].loss_before
    assert hist_q[-1].loss_after == pytest.approx(
        hist_id[-1].loss_after, rel=0.25
    )


# ---------------------------------------------------------------------------
# FedConfig validation (satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kwargs, match",
    [
        (dict(correction="fancy"), "correction"),
        (dict(num_clients=0), "num_clients"),
        (dict(num_clients=-3), "num_clients"),
        (dict(s_star=0), "s_star"),
        (dict(lr=0.0), "lr"),
        (dict(lr=-1e-3), "lr"),
        (dict(tau=1.0), "tau"),
        (dict(tau=-0.1), "tau"),
    ],
)
def test_fedconfig_rejects_bad_hyperparameters(kwargs, match):
    good = dict(num_clients=4, s_star=2)
    good.update(kwargs)
    with pytest.raises(ValueError, match=match):
        FedConfig(**good)


def test_fedconfig_accepts_boundary_values():
    FedConfig(num_clients=1, s_star=1, lr=1e-8, tau=0.0)
    FedConfig(num_clients=4, s_star=2, tau=0.999)


def test_per_client_bytes_beyond_int32_leave_jit():
    """A 7B model's identity payload (> 2 GiB per client) must not overflow
    the int32 a python int becomes when it leaves jit."""
    from repro.core.round import _per_client_bytes

    assert _per_client_bytes(100, 8, 4) == 102  # exact int in range
    big = jax.jit(lambda: _per_client_bytes(5_606_497_048, 8, 4))()
    assert big.dtype == jnp.float32
    assert float(big) == pytest.approx(5_606_497_050, rel=1e-7)
