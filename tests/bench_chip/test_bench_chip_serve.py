"""The serving cell's readings from a hand-made log: the tails, what the
reference checks, and the garbage collector's passes."""
import gc
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import harness  # noqa: E402

kind = harness.Bench().kind("serve_open_loop")


def _log():
    log = kind.Log()
    # request 0: arrives at 0, tokens at 0.1, 0.2, 0.4 and one after the close
    # request 1: arrives at 0.5, tokens at 0.8 and 0.9
    # request 2: arrives at 0.9 and never gets a token
    log.arrival = {0: 0.0, 1: 0.5, 2: 0.9}
    log.prompt = {0: np.arange(5), 1: np.arange(40), 2: np.arange(3)}
    log.tokens = {0: [0.1, 0.2, 0.4, 1.5], 1: [0.8, 0.9]}
    log.done = {
        0: SimpleNamespace(tokens=np.arange(4), queued_s=0.01),
        1: SimpleNamespace(tokens=np.arange(2), queued_s=0.03),
    }
    return log


def test_summary_counts_missing_first_tokens_and_only_tokens_in_the_window():
    got = kind.summarise(_log(), seconds=1.0)
    assert math.isinf(got["ttft_p95_ms"])  # request 2 is missing
    assert got["requests"] == 3 and got["started"] == 2 and got["finished"] == 2
    assert got["serve_tokens_per_s"] == 5.0  # 1.5 s lies after the close
    # gaps inside the window: 0.1, 0.2 (request 0) and 0.1 (request 1)
    assert got["itl_p95_ms"] == pytest.approx(1e3 * np.percentile([0.1, 0.2, 0.1], 95))
    log = _log()
    log.tokens[2] = [1.2]
    assert kind.summarise(log, 1.0)["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile([0.1, 0.3, 0.3], 95))


def test_the_sample_holds_the_longest_request_and_enough_tokens():
    log = _log()
    assert kind.sample(log, seed=5, want_tokens=1) == [1]  # 40 + 2 positions
    assert sorted(kind.sample(log, seed=5, want_tokens=6)) == [0, 1]
    assert kind.sample(kind.Log(), seed=5, want_tokens=6) == []


def test_garbage_collector_passes_are_recorded_while_installed():
    with kind.GcPauses() as pauses:
        gc.collect()
    assert pauses.pauses and pauses.pauses[-1][0] == 2
    n = len(pauses.pauses)
    gc.collect()
    assert len(pauses.pauses) == n and pauses not in gc.callbacks
