"""Emulation kernels' share of their roofline in training: the least
time of the logical products they emulate (every projection and the
head of each step's forward, [batch*seq, K] @ [K, N], at the bf16 peak or
bf16 bytes over peak bandwidth), over the device time of the Pallas
events in the traced window."""
import flops


def read(r):
    t = r.trace or {}
    if not t.get("pallas_s"):
        return None
    rows = int(r.traffic["batch"]) * int(r.traffic["seq"])
    least = r.counters["steps"] * flops.model_matmuls_roofline_s(r.cfg, rows, r.peaks)
    return 100.0 * least / t["pallas_s"]
