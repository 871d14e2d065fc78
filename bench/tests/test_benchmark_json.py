"""BENCHMARK.json keeps to the shape the benchmark's runner relies on:
names, units, keys, files found by name, and metrics tied to cells."""
import re

import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_shape():
    b = tiny.bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"] and b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    for group, keys in KEYS.items():
        seen = set()
        for e in b[group]:
            assert set(e) - {"workloads"} == keys, (group, e)
            assert NAME.match(e["name"]) and e["name"] not in seen
            seen.add(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


def test_cells_and_metrics_are_found():
    b = tiny.bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert (tiny.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (tiny.BENCH / "limits" / f"{w['name']}.json").is_file()
        assert w["chips"] in (1, 4)
        mine = [n for n, m in e2e.items() if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        per = [m for m in b["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
        assert per
        for m in per:
            assert m["moves"] in mine
    for m in b["per_layer"]:
        assert (tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
    layers = {m["layer"] for m in b["per_layer"]}
    perf = (tiny.ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf
