"""A whole run of a cell on the CPU at a size a test holds: the check that
decides ``correct`` passes a sound run, and fails the control and every
fault the cell can have.

The harness's look for a chip is skipped; everything else is the run as
the chip would make it.  The faults are planted in the program, under
the timed path: a token altered where it is sampled, and a decode step
that returns its cache unchanged.  The control is the reference computed
in the precision below the configuration's (bfloat16 for this float32
configuration) put in the program's place.
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_chip_tiny as tiny  # noqa: E402


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.make_tree(tmp_path_factory.mktemp("bench"))


def test_a_sound_traced_serving_run_is_correct(tree):
    rc, res, err = tiny.run_cell(*tree, seed=2**31 + 5, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["attempted"] >= 5 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(tiny.SERVE_LIMITS)
    assert set(res["metrics"]) == {"device_idle_share.serve", "serve_decode_mfu"}
    assert 0 <= res["metrics"]["device_idle_share.serve"]["value"] < 100
    assert 0 < res["metrics"]["serve_decode_mfu"]["value"] < 100
    dev = res["device"]
    assert dev["platform"] == "cpu" and 0 < dev["busy_s"] <= dev["window_s"]
    assert res["breakdown"]["device_ops"] and len(res["breakdown"]["device_ops"]) <= 10
    assert err.strip().splitlines()[-1].startswith("check ")


def test_a_serving_run_reports_its_end_to_end_metrics(tree):
    rc, res, err = tiny.run_cell(*tree, seed=9)
    assert rc == 0 and res["correct"] is True, err[-3000:]
    m = res["metrics"]
    assert set(m) == {"itl_p95_ms", "setup_s"}
    assert all(0 < v["value"] < float("inf") for v in m.values())
    assert "garbage collector in the window" in err


def _altered_token(monkeypatch):
    import numpy as np

    from repro.serve.engine import ServeEngine

    real = ServeEngine.sample

    def sample(self, logits, rids, steps):
        return (np.asarray(real(self, logits, rids, steps)) + 1) % logits.shape[-1]

    monkeypatch.setattr(ServeEngine, "sample", sample)


def _stale_cache(monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine.step

    def step(self, state, last_tokens):
        logits, _ = real(self, state, last_tokens)
        return logits, state

    monkeypatch.setattr(ServeEngine, "step", step)


@pytest.mark.parametrize("plant", [_altered_token, _stale_cache],
                         ids=["token_altered", "state_unchanged"])
def test_a_planted_serving_fault_makes_the_run_incorrect(tree, monkeypatch, plant):
    plant(monkeypatch)
    rc, res, err = tiny.run_cell(*tree, seed=13)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]


def test_the_serving_control_is_not_correct(tree):
    """The reference in bfloat16 picks tokens whose float32 logits lie
    below the best by more than the limit, on three seeds."""
    import numpy as np

    from benchmarks.chip import harness

    bench = harness.Bench(*tree)
    cell = bench.cell(tiny.SERVE_CELL)
    kind = bench.kind("serve_open_loop")
    for seed in (31, 32, 2**31 + 33):
        rng = np.random.default_rng(seed)
        seqs = [(rng.integers(0, 512, 40).astype(np.int32), rng.integers(0, 512, 16))
                for _ in range(16)]
        gaps = kind.logit_gaps(cell, seed, seqs, mode="bfloat16")
        assert max(float(g.max()) for g in gaps) > tiny.SERVE_LIMITS["logit_gap"]
