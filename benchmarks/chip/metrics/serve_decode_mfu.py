"""Share of the chip's peak that the window's decode steps required: per
step the larger of its FLOPs over peak FLOP/s and its bytes (as the
configuration's architecture file counts them: for a dense decoder the
factors once, each sequence's KV up to the position read) over peak HBM
bandwidth (``counts.decode_step_cost``), summed over the steps that ended
in the window, over the window."""
from benchmarks.chip import counts


def read(rec):
    log = rec.extra.get("serve_log")
    if log is None or rec.window_s <= 0:
        return None
    peak = counts.peaks(rec.device_kind, rec.peaks_file)
    total = 0.0
    for t, contexts, _, _, _ in log.steps:
        if contexts and t <= rec.window_s:
            f, b = counts.decode_step_cost(rec.cell.config, contexts, root=rec.cell.root)
            total += max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
    return 100.0 * total / rec.window_s
