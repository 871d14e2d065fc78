#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine and print its result.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything it
needs is found by name: its configuration in ``bench/configs/<config>.json``,
its traffic or training job in ``bench/traffic/<traffic>.json`` (whose
``kind`` picks the job driver in ``bench/jobs/``), its correctness limits
in ``bench/limits/<cell>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

One run is one process.  It makes weights and inputs from ``--seed``,
warms up the cell's own programs (set-up, timed as ``setup_s``), measures
for ``--seconds``, then checks what the timed path produced against the
plain reference.  With ``--trace 1`` the window runs under the profiler and
the per-layer metrics are reported instead of the end-to-end ones.  The
last line of standard output is one JSON object; the numbers compared for
``correct`` and their limits are the last lines of standard error and the
last key of that object.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.

``--control`` (not part of a benchmark run) also puts each control of the
job (the reference at a lower precision, or with part of its work left
out) in the program's place, and reports its numbers and whether the
cell's limits find it correct, which they must not; see PERF.md.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Context:
    """What a job driver gets, and what it fills in."""

    def __init__(self, cell: Dict, cfg: Dict, traffic: Dict, seed: int,
                 seconds: float, trace: bool, control: bool = False,
                 scratch: Optional[Path] = None):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control = control
        self.scratch = scratch or (ROOT / "bench_out" / cell["name"])
        self.scratch.mkdir(parents=True, exist_ok=True)
        self.trace_dir = self.scratch / "trace"
        self.setup: Dict[str, float] = {}
        self.counters: Dict = {}
        self.e2e: Dict[str, float] = {}
        self.control_numbers: Dict = {}
        self.attempted = self.failed = 0
        self.window = {}
        self.peak_bytes = None
        self._annotation = None

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def start_window(self) -> None:
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # device ops and the harness's own spans only: the python
            # tracer and the finer host levels slow the host loop itself
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()
        self.window["t0"] = time.perf_counter()
        self.setup["setup_s"] = self.window["t0"] - T_START

    def stop_window(self) -> None:
        import jax

        self.window["t1"] = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        if self.trace:
            jax.profiler.stop_trace()

    def memory_peak(self) -> None:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.peak_bytes = max(peaks) if peaks else None


class Record:
    """What a per-layer metric's reader sees."""

    def __init__(self, ctx: Context, peaks: Dict, trace: Optional[Dict]):
        self.cfg, self.traffic, self.cell = ctx.cfg, ctx.traffic, ctx.cell
        self.counters, self.e2e, self.peaks, self.trace = ctx.counters, ctx.e2e, peaks, trace


def load_json(path: Path) -> Dict:
    return json.loads(path.read_text())


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: Dict, cell: str, per_layer: bool):
    """The metrics a cell reports: end-to-end ones with ``--trace 0``,
    per-layer ones with ``--trace 1``."""
    group = bench["per_layer"] if per_layer else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def resolve(workload: str):
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")["limits"]
    return bench, cell, cfg, traffic, limits


def job_for(traffic: Dict):
    spec = importlib.util.spec_from_file_location(
        f"bench_job_{traffic['kind']}", BENCH / "jobs" / f"{traffic['kind']}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.run


def execute(bench: Dict, cell: Dict, cfg: Dict, traffic: Dict, limits: Dict,
            ctx: Context) -> Dict:
    """Run the cell in this process and build its result (nothing printed)."""
    import jax

    import checks
    from peaks import peaks_for

    devs = jax.devices()
    dev = devs[0]
    numbers = job_for(traffic)(ctx)
    ctx.log("numbers " + json.dumps(numbers))
    limits = {k: float(v) for k, v in limits.items()}
    correct = checks.judge(numbers, limits)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": ctx.peak_bytes}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if ctx.trace:
        spec = importlib.util.spec_from_file_location("bench_trace", BENCH / "trace.py")
        T = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(T)
        red = T.reduce_dir(ctx.trace_dir)
        ctx.log("device lines " + json.dumps(red["lines"]))
        ctx.log("pallas ops " + json.dumps(red["pallas_ops"]))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = red["breakdown"]
        rec = Record(ctx, peaks_for(dev.device_kind), red)
        for m in cell_metrics(bench, cell["name"], per_layer=True):
            v = load_reader(m["name"])(rec)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(ctx.e2e, setup_s=ctx.setup["setup_s"])
        for m in cell_metrics(bench, cell["name"], per_layer=False):
            if m["name"] in values and math.isfinite(values[m["name"]]):
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(ctx.attempted),
              "failed": int(ctx.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if ctx.control_numbers:
        result["control"] = {name: dict(nums, correct=checks.judge(nums, limits))
                             for name, nums in ctx.control_numbers.items()}
    result["checks"] = {k: {"value": numbers.get(k), "limit": float(v)}
                        for k, v in limits.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also run the job's controls and judge them by the cell's limits")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout; nothing "
              "was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    bench, cell, cfg, traffic, limits = resolve(args.workload)

    import jax

    from repro.launch import compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"found {len(devs)} {devs[0].platform} device(s); nothing was run",
              file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    ctx = Context(cell, cfg, traffic, args.seed, args.seconds, bool(args.trace),
                  control=args.control)
    ctx.log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
            f"trace {args.trace} compile cache {cache}")
    result = execute(bench, cell, cfg, traffic, limits, ctx)
    ctx.log("setup " + json.dumps({k: round(v, 4) for k, v in ctx.setup.items()}))
    ctx.log("counters " + json.dumps(
        {k: v for k, v in ctx.counters.items() if not isinstance(v, list)}))
    ctx.log("e2e " + json.dumps(ctx.e2e))
    for name, nums in result.get("control", {}).items():
        ctx.log(f"control {name} " + json.dumps(nums))
    gc.collect()
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
