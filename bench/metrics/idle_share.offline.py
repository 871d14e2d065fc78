"""Share of the traced serving window with no operation on the device."""


def read(r):
    t = r.trace or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
