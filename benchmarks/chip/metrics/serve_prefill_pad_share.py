"""Share of the window's prefill positions that are bucket padding:
100 × (Σ bucket − Σ prompt_len) / Σ bucket over the ``serve.admit``
spans, positions the prefill computes for nothing."""
from benchmarks.chip import spans


def read(rec):
    return spans.prefill_pad_share(spans.of_record(rec))
