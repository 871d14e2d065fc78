"""The split of the device's idle time by the program's own spans
(``bench/span_trace.py``)."""
import importlib.util
import json

import pytest

import tiny

_spec = importlib.util.spec_from_file_location("bench_span_trace", tiny.BENCH / "span_trace.py")
S = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(S)
T = S.T


def test_split_by_hand():
    ops = {"/device:TPU:0": [
        ("fusion.1", 1.2, 1.5),          # prefill, issued inside engine.prefill
        ("fusion.2", 2.1, 2.4),          # decode; 2.4-2.8 is lost inside the wait
        ("copy.3", 2.8, 3.0),            # decode's copy, still inside its wait
        ("fusion.4", 3.6, 3.7),          # the reset
    ]}
    host = [
        ("bench.window", 1.0, 5.0),
        ("bench.engine_step", 1.0, 4.0),
        ("engine.step", 1.05, 3.95),
        ("engine.admit", 1.1, 1.9),
        ("engine.prefill", 1.15, 1.6),
        ("engine.prefill.dispatch", 1.15, 1.2),
        ("engine.prefill.wait", 1.2, 1.6),
        ("engine.decode", 2.0, 3.05),
        ("engine.decode.dispatch", 2.0, 2.05),
        ("engine.decode.wait", 2.05, 3.05),
        ("engine.sample", 3.1, 3.8),
        ("engine.reset", 3.5, 3.75),
        ("engine.reset.wait", 3.55, 3.75),
    ]
    red = T.reduce_events(ops, [h for h in host if h[0].startswith("bench.")])
    mine = S.reduce_spans(ops, host)
    assert mine["host_idle_s"] + mine["wait_idle_s"] == pytest.approx(
        red["window_s"] - red["busy_s"])
    # inside waits: 1.5-1.6, 2.05-2.1, 2.4-2.8, 3.0-3.05, 3.55-3.6, 3.7-3.75
    assert mine["wait_idle_s"] == pytest.approx(0.1 + 0.05 + 0.4 + 0.05 + 0.05 + 0.05)
    # the admission loop's own idle time leaves out its prefill's
    assert mine["idle_by_span"]["engine.admit"] == pytest.approx(0.05 + 0.3)
    assert mine["idle_by_span"]["no span"] == pytest.approx(1.0)   # 4.0-5.0
    # each gap is named by the innermost span at its middle, of either kind
    gaps = [(n, round(s, 6)) for n, s, _ in mine["idle_gaps"]]
    assert gaps[0] == ("no span", 1.3)                       # 3.7-5.0
    assert set(gaps[1:3]) == {("engine.admit", 0.6),         # 1.5-2.1
                              ("engine.sample", 0.6)}        # 3.0-3.6
    assert gaps[3] == ("engine.decode.wait", 0.4)            # 2.4-2.8
    # ops go to the innermost program span open at their start
    by = mine["ops_by_span"]
    assert by["engine.prefill.wait"]["device_s"] == pytest.approx(0.3)
    assert by["engine.decode.wait"]["top"][0] == ["fusion.2", pytest.approx(0.3)]
    assert by["engine.decode.wait"]["device_s"] == pytest.approx(0.5)
    assert by["engine.reset.wait"]["device_s"] == pytest.approx(0.1)


def test_serve_readings():
    spans = {
        "engine.admit": {"count": 10, "total_s": 0.5, "self_s": 0.2},
        "engine.fetch": {"count": 12, "total_s": 0.01, "self_s": 0.01},
        "engine.sample": {"count": 12, "total_s": 0.05, "self_s": 0.02},
        "engine.reset": {"count": 2, "total_s": 0.03, "self_s": 0.0},
        "engine.decode.dispatch": {"count": 10, "total_s": 0.004, "self_s": 0.004},
        "engine.prefill.dispatch": {"count": 2, "total_s": 0.001, "self_s": 0.001},
        "engine.compile.dispatch": {"count": 1, "total_s": 9.0, "self_s": 9.0},
    }
    got = S.serve_readings(spans, engine_steps=10)
    assert got == pytest.approx({"queue_scan_ms.serve": 20.0, "sample_ms.serve": 3.0,
                                 "reset_ms.serve": 3.0, "dispatch_ms.serve": 0.5})


@pytest.mark.parametrize("which", ["train", "serve", "serve_spans"])
def test_recorded_trace(which):
    """Slices of real traces on one v5e (each window's first 0.4 s; the
    serving one with the program's spans holds the first engine step's
    sixteen prefills): the split adds up to the idle time that
    ``trace.py`` finds, with or without program spans."""
    path = tiny.BENCH / "tests" / "data" / f"trace_sample_{which}.json"
    data = json.loads(path.read_text())
    if "names" in data:
        ops, host = S.load_slice(data)
    else:
        ops = {k: [tuple(e) for e in v] for k, v in data["device_ops"].items()}
        host = [tuple(h) for h in data["host_spans"]]
    red = T.reduce_events(ops, [h for h in host if h[0].startswith("bench.")])
    mine = S.reduce_spans(ops, host)
    assert mine["host_idle_s"] + mine["wait_idle_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)
    assert sum(v["device_s"] for v in mine["ops_by_span"].values()) == pytest.approx(
        sum(red["op_s"].values()))
    if which == "serve_spans":
        assert all(n.startswith(("engine.", "bench.")) for n, _, _ in mine["idle_gaps"])
        assert mine["wait_idle_s"] > 0.0
        assert mine["ops_by_span"]["engine.prefill.wait"]["device_s"] > 0.9 * red["busy_s"]
    else:
        assert mine["wait_idle_s"] == 0.0


def test_traced_tiny_serving_run(tmp_path, monkeypatch):
    """The tool runs the serving job traced and reads the engine's span
    table over the window (on the CPU: no device plane, so no idle split,
    and the chip's peaks stand in for the CPU's)."""
    import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    run = S._load("run")
    c, cfg, traffic = tiny.serve_parts()
    out = S.traced_run(run, tiny.bench(), c, cfg, traffic, seed=3, seconds=1.0,
                       scratch=tmp_path)
    spans = out["spans"]["spans"]
    assert spans["engine.step"]["count"] == out["counters"]["engine_steps"] > 0
    assert "engine.compile" not in spans
    for name in ("queue_scan_ms.serve", "sample_ms.serve", "reset_ms.serve",
                 "dispatch_ms.serve", "engine_host_ms.serve"):
        assert out["metrics"][name] >= 0.0
    m = out["metrics"]
    assert m["queue_scan_ms.serve"] + m["sample_ms.serve"] + m["reset_ms.serve"] <= (
        m["engine_host_ms.serve"])
