"""Whole serving path's share of the chip's bf16 peak: forward flops of
every prompt token prefilled and every output token decoded in the
window (projections, head and attention at each token's context), per
second of the window, over the peak."""
import flops


def read(r):
    c = r.counters
    if not c.get("window_s"):
        return None
    contexts = list(c["decode_contexts"])
    for p in c["prefill_lengths"]:
        contexts.extend(range(1, p + 1))
    work = flops.serve_flops(r.cfg, contexts)
    return 100.0 * work / c["window_s"] / (r.peaks["bf16_flops"] * int(r.cell["chips"]))
