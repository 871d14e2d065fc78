"""The reduction from a profiler trace to busy time, idle gaps, Pallas
time and the breakdown."""
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import importlib.util

_spec = importlib.util.spec_from_file_location("bench_trace", tiny.BENCH / "trace.py")
T = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(T)


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert T.union_length(iv) == pytest.approx(3.0)
    assert T.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]


K = ('%closed_call.1 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p), '
     'custom_call_target="tpu_custom_call"')


def test_reduce_events_by_hand():
    ops = {"/device:TPU:0": [
        ("fusion.1", 0.5, 1.5),          # half outside the window
        (K, 2.0, 3.0),                  # a Pallas kernel
        (K, 2.5, 3.5),                  # overlaps the last one
        ("convolution.2", 6.0, 6.5),
        ("copy.3", 20.0, 21.0),          # after the window
    ]}
    host = [
        ("bench.window", 1.0, 10.0),
        ("bench.engine_step", 1.0, 5.0),
        ("bench.submit", 3.6, 3.7),
    ]
    red = T.reduce_events(ops, host)
    assert red["window_s"] == pytest.approx(9.0)
    assert red["busy_s"] == pytest.approx(0.5 + 1.5 + 0.5)
    assert red["pallas_s"] == pytest.approx(2.0)
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert names[0] == "closed_call.1 (pallas)"
    gaps = red["breakdown"]["idle_gaps"]
    # gaps: 1.5-2.0 (engine_step), 3.5-6.0 (submit is innermost at 4.75? no:
    # its span ends at 3.7, so engine_step), 6.5-10.0 (no span)
    assert gaps[0] == ["no span", pytest.approx(3.5)]
    assert gaps[1] == ["bench.engine_step", pytest.approx(2.5)]
    assert gaps[2] == ["bench.engine_step", pytest.approx(0.5)]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_events({}, [("bench.engine_step", 0.0, 1.0)])


def _grid_busy(ops, lo, hi, step=1e-6):
    """Busy time by counting covered points of a fine grid (an
    independent way to take the union)."""
    import numpy as np

    n = int(round((hi - lo) / step))
    covered = np.zeros(n, bool)
    for _, a, b in ops:
        i, j = int(np.ceil((max(a, lo) - lo) / step)), int(np.ceil((min(b, hi) - lo) / step))
        if j > i:
            covered[i:j] = True
    return covered.sum() * step


@pytest.mark.parametrize("which", ["train", "serve"])
def test_recorded_trace(which):
    """A slice of a real trace of each cell on one v5e (the window's first
    0.4 s): the reduction agrees with an independent count."""
    import json

    data = json.loads((tiny.BENCH / "tests" / "data" / f"trace_sample_{which}.json").read_text())
    ops = {k: [tuple(e) for e in v] for k, v in data["device_ops"].items()}
    host = [tuple(h) for h in data["host_spans"]]
    red = T.reduce_events(ops, host)
    lo, hi = host[0][1], host[0][2]
    (dev_ops,) = ops.values()
    assert red["window_s"] == pytest.approx(hi - lo)
    assert red["busy_s"] == pytest.approx(_grid_busy(dev_ops, lo, hi), abs=2e-4)
    pallas = sum(min(b, hi) - max(a, lo) for n, a, b in dev_ops
                 if T.is_pallas(n) and min(b, hi) > max(a, lo))
    assert red["pallas_s"] == pytest.approx(pallas)
    assert 0 < red["pallas_s"] <= red["busy_s"] <= red["window_s"]
    idle = sum(s for _, s in red["breakdown"]["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
