"""Share of the engine's timed device calls in the window that went to
prefill: delta of Engine.prefill_s over delta of prefill_s + decode_s."""


def read(r):
    c = r.counters
    total = c.get("prefill_s", 0.0) + c.get("decode_s", 0.0)
    if total <= 0:
        return None
    return 100.0 * c["prefill_s"] / total
