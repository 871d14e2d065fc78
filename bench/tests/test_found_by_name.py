"""A cell, a traffic mix and a per-layer metric added as new files plus
``BENCHMARK.json`` entries are found by name, with no edit to a file the
benchmark already has."""
import hashlib
import importlib.util
import json
import shutil

import tiny


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_found_without_edits(tmp_path):
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path / "bench")
    b = (tmp_path / "bench")

    # the new files: a configuration, a traffic mix, limits, a metric reader
    cfg = json.loads((b / "configs" / "yi-6b.json").read_text())
    cfg["name"] = "yi-6b-alt"
    (b / "configs" / "yi-6b-alt.json").write_text(json.dumps(cfg))
    traffic = json.loads((b / "traffic" / "serve.exact-backlog.json").read_text())
    traffic["prompt_len"]["median"] = 64
    (b / "traffic" / "serve.exact-short.json").write_text(json.dumps(traffic))
    (b / "limits" / "yi-6b-alt.serve.exact-short.json").write_text(
        json.dumps({"limits": {"logit_gap": 1.0}}))
    (b / "metrics" / "slot_use.serve.py").write_text(
        "def read(r):\n    return 100.0 * r.counters['active'] / r.counters['slots']\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="yi-6b-alt",
                                 file="bench/configs/yi-6b-alt.json"))
    bench["workloads"].append({"name": "yi-6b-alt.serve.exact-short", "config": "yi-6b-alt",
                               "traffic": "serve.exact-short", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "slot_use.serve", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "serving scheduler",
                               "moves": "serve_tok_s",
                               "workloads": ["yi-6b-alt.serve.exact-short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = importlib.util.spec_from_file_location("bench_run_copy", b / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run.ROOT == tmp_path
    got, c, cfg2, traffic2, limits = run.resolve("yi-6b-alt.serve.exact-short")
    assert cfg2["name"] == "yi-6b-alt" and traffic2["prompt_len"]["median"] == 64
    assert limits == {"logit_gap": 1.0}
    names = [m["name"] for m in run.cell_metrics(got, c["name"], per_layer=True)]
    assert names == ["slot_use.serve"]
    read = run.load_reader("slot_use.serve")

    class R:
        counters = {"active": 3, "slots": 4}

    assert read(R) == 75.0
    assert run.job_for(traffic2).__module__ == "bench_job_serve"
    after = _digest(b)
    assert {k: v for k, v in after.items() if k in before} == before
