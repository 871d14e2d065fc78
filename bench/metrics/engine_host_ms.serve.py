"""Host time per engine step: the window's wall time less the engine's
timed prefill and decode calls (each waits for its device result), over
the engine steps run in the window."""


def read(r):
    c = r.counters
    if not c.get("engine_steps"):
        return None
    host = c["window_s"] - c["prefill_s"] - c["decode_s"]
    return 1e3 * host / c["engine_steps"]
