"""Whole training step's share of the chip's bf16 peak: model flops per
token (forward and backward of every projection, the head and causal
attention; recomputation not counted) times the traced run's trained
tokens per second, over the peak of the chips used."""
import flops


def read(r):
    tok_s = r.e2e.get("train_tok_s")
    if not tok_s:
        return None
    per_tok = flops.train_flops_per_token(r.cfg, int(r.traffic["seq"]))
    return 100.0 * per_tok * tok_s / (r.peaks["bf16_flops"] * int(r.cell["chips"]))
