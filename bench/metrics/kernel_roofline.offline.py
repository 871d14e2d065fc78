"""Serving kernels' share of their roofline: the least time of the
logical work of every Pallas call in the window, over the device time of
the Pallas events.  Each decode step runs flash decode on every slot at
its context.  Under an emulated backend the projections are Pallas
kernels too: each decode step runs every projection and the head on all
slots ([n_slots, K] @ [K, N]), each prefill on its padded bucket; under
the exact backend they are XLA products and not counted."""
import flops


def read(r):
    t, c = r.trace or {}, r.counters
    if not t.get("pallas_s"):
        return None
    slots, steps = int(c["n_slots"]), int(c["decode_steps"])
    idle_rows = max(steps * slots - len(c["decode_contexts"]), 0)
    least = flops.flash_decode_roofline_s(
        r.cfg, list(c["decode_contexts"]) + [0] * idle_rows, r.peaks)
    if r.traffic["backend"] != "exact":
        least += steps * flops.model_matmuls_roofline_s(r.cfg, slots, r.peaks)
        least += sum(flops.model_matmuls_roofline_s(r.cfg, b, r.peaks)
                     for b in c["prefill_buckets"])
    return 100.0 * least / t["pallas_s"]
