"""FLOP and byte counts against hand counts at a reduced configuration,
the counts of the chat cell's configuration pinned to the integers they
had before the architecture file held them, and the peak table."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import counts  # noqa: E402
from benchmarks.chip.references import dense_lm  # noqa: E402

REPO = Path(__file__).resolve().parents[2]

# d 64, 4 heads of 16 (2 kv heads), ff 128, vocab 256, 2 layers; rank
# budget 1/4 of the smaller side, so r = 16 for d×d, 8 for d×32 (k, v)
CFG = {
    "architecture": "dense_lm",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "qkv_bias": True, "lowrank": {"rank_frac": 0.25, "r_cap": 256, "min_dim": 32},
}


def test_layout_ranks_follow_the_policy():
    r = {lin.path: lin.r for lin in dense_lm.linears(CFG)}
    assert r == {
        "embed": 16, "lm_head": 16, "blocks/attn/q": 16, "blocks/attn/k": 8,
        "blocks/attn/v": 8, "blocks/attn/o": 16, "blocks/mlp/gate": 16,
        "blocks/mlp/up": 16, "blocks/mlp/down": 16,
    }


def test_decode_step_cost_by_hand():
    flops, nbytes = counts.decode_step_cost(CFG, [3, 5])
    B, L, hd, H, Hkv = 2, 2, 16, 4, 2
    lin = lambda K, N, R: (B * 2 * (K * R + R * R + R * N), 2 * (K * R + R * R + N * R))
    parts = [lin(64, 64, 16)] * 2 + [lin(64, 32, 8)] * 2 + [lin(64, 128, 16)] * 2 + [lin(128, 64, 16)]
    f = L * sum(p[0] for p in parts) + lin(64, 256, 16)[0]
    b = L * sum(p[1] for p in parts) + lin(64, 256, 16)[1]
    f += B * 2 * (16 * 16 + 16 * 64)
    b += 2 * (B * 16 + 16 * 16 + 64 * 16)
    f += L * 2 * (2 * hd * H * 8)
    b += L * 2 * 2 * Hkv * hd * 8
    assert (flops, nbytes) == (f, b)


# (contexts, (flops, bytes)) of configs/qwen2-7b.json, as the counts read
# when they lived in counts.py
QWEN2_7B = [
    ([1], (1304764416, 1304420864)),
    ([70, 285, 1024, 2048], (6593077248, 1500882944)),
    (list(range(1, 2049, 64)), (54494756864, 3126542336)),
]


@pytest.mark.parametrize("contexts,want", QWEN2_7B, ids=["one", "four", "thirty-two"])
def test_decode_step_cost_of_qwen2_7b_is_pinned(contexts, want):
    cfg = json.loads((REPO / "benchmarks/chip/configs/qwen2-7b.json").read_text())
    assert counts.decode_step_cost(cfg, contexts) == want


def test_peaks_known_and_unknown(tmp_path):
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        counts.peaks("TPU v9 imaginary")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"source": "test", "devices": {}}))
    with pytest.raises(KeyError):
        counts.peaks("TPU v5 lite", table)
