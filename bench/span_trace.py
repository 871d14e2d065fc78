#!/usr/bin/env python3
"""The program's own spans in a profiler trace: what the host was doing
while the device sat idle, and which call issued each operation.

``trace.py`` reads the harness's ``bench.*`` spans only.  This module also
reads the program's (``engine.*``, ``trainer.*``; see
``repro.runtime.spans``), all on the profiler's clock, and splits the
window's idle device time by the innermost span open at each moment:

* ``wait_idle_s``: idle time inside a ``*.wait`` span.  The host was
  blocked on the device there, so the device was working: the trace lost
  those operations, or the device stalled;
* ``host_idle_s``: idle time outside every ``*.wait`` span, the host's
  doing.  The two add up to the window less ``busy_s``.

It also names each long idle gap by the innermost span of either kind open
at its middle (with its length and its start in the window), and sums the
device time of the operations by the innermost program span open at each
one's middle.  The middle and not the start: the trace places the device's
operations about a millisecond early against the host's spans (PERF.md
§5), so a call's first operations would fall in the span before it.

Run one cell traced, as ``run.py --trace 1`` does, and print these with
the change of the program's span tables over the window and the serving
scheduler's readings (PERF.md §3):

  python3 bench/span_trace.py --workload <cell> --seed <n> --seconds <s> \\
      [--out <json>] [--sample <json>]

``--sample`` writes the window's first 0.4 s for the tests: the device
operations (each named by its instruction, Pallas kernels marked as
``trace.is_pallas`` reads them) and every host span, in nanoseconds from
the window's start.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

BENCH = Path(__file__).resolve().parent
PROGRAM = ("engine.", "trainer.")
Span = Tuple[str, float, float]


def _load(name: str):
    spec = importlib.util.spec_from_file_location(f"bench_{name}", BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


T = _load("trace")


def segments(spans: List[Span], lo: float, hi: float) -> List[Span]:
    """[lo, hi] cut at every span boundary, each piece named by the
    innermost (shortest) span open over it, or "no span"."""
    cuts = sorted({lo, hi, *(t for _, a, b in spans for t in (a, b) if lo < t < hi)})
    starts = sorted(spans, key=lambda s: s[1])
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        while i < len(starts) and starts[i][1] <= mid:
            open_.append(starts[i])
            i += 1
        open_ = [s for s in open_ if s[2] > mid]
        inner = min(open_, key=lambda s: s[2] - s[1])[0] if open_ else "no span"
        out.append((inner, a, b))
    return out


def by_piece(pieces: List[Span], intervals: List[Tuple[float, float]]) -> Dict[str, float]:
    """Seconds of the (disjoint) ``intervals`` under each piece's name."""
    out: Dict[str, float] = {}
    iv, j = sorted(intervals), 0
    for name, a, b in pieces:
        while j < len(iv) and iv[j][1] <= a:
            j += 1
        k = j
        while k < len(iv) and iv[k][0] < b:
            cut = min(b, iv[k][1]) - max(a, iv[k][0])
            if cut > 0:
                out[name] = out.get(name, 0.0) + cut
            k += 1
    return out


def names_at(pieces: List[Span], times: List[float]) -> List[str]:
    """The piece's name at each time (``pieces`` in order, covering)."""
    order = sorted(range(len(times)), key=lambda i: times[i])
    out, j = [""] * len(times), 0
    for i in order:
        while j < len(pieces) - 1 and pieces[j][2] <= times[i]:
            j += 1
        out[i] = pieces[j][0]
    return out


def reduce_spans(device_ops: Dict[str, List], host_spans: List[Span]) -> Dict:
    """``device_ops`` as for ``trace.reduce_events``; ``host_spans`` holds
    the ``bench.*`` spans and the program's."""
    lo, hi = next((a, b) for n, a, b in host_spans if n == "bench.window")
    spans = [s for s in host_spans if s[0] != "bench.window"]
    pieces = segments(spans, lo, hi)
    program = segments([s for s in spans if s[0].startswith(PROGRAM)], lo, hi)
    idle: Dict[str, float] = {}
    ops_by: Dict[str, Dict[str, float]] = {}
    all_gaps = []
    for ops in device_ops.values():
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in ops if min(b, hi) > max(a, lo)]
        gaps = T.gaps([(a, b) for _, a, b in ops], lo, hi)
        all_gaps += gaps
        for k, v in by_piece(pieces, gaps).items():
            idle[k] = idle.get(k, 0.0) + v
        for who, (name, a, b) in zip(names_at(program, [(a + b) / 2 for _, a, b in ops]), ops):
            row = ops_by.setdefault(who, {})
            key = T.short_name(name)
            row[key] = row.get(key, 0.0) + (b - a)
    n_dev = max(len(device_ops), 1)
    wait = sum(v for k, v in idle.items() if k.endswith(".wait"))
    top = sorted(all_gaps, key=lambda g: g[1] - g[0], reverse=True)[:10]
    return {
        "host_idle_s": (sum(idle.values()) - wait) / n_dev,
        "wait_idle_s": wait / n_dev,
        "idle_by_span": {k: v / n_dev for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[n, b - a, a - lo] for n, (a, b) in
                      zip(names_at(pieces, [(a + b) / 2 for a, b in top]), top)],
        "ops_by_span": {
            who: {"device_s": sum(row.values()) / n_dev,
                  "top": [[k, v / n_dev] for k, v in sorted(row.items(), key=lambda kv: -kv[1])[:5]]}
            for who, row in sorted(ops_by.items(), key=lambda kv: -sum(kv[1].values()))},
    }


def serve_readings(spans: Dict[str, Dict], engine_steps: int) -> Dict[str, float]:
    """The serving scheduler's readings (ms per engine step) from the
    change of the engine's span table over the window."""
    def get(name, key="total_s"):
        return spans.get(name, {}).get(key, 0.0)

    per = 1e3 / max(engine_steps, 1)
    dispatch = sum(v["total_s"] for k, v in spans.items()
                   if k.endswith(".dispatch") and not k.startswith("engine.compile"))
    return {
        "queue_scan_ms.serve": per * get("engine.admit", "self_s"),
        "sample_ms.serve": per * (get("engine.fetch") + get("engine.sample", "self_s")),
        "reset_ms.serve": per * get("engine.reset"),
        "dispatch_ms.serve": per * dispatch,
    }


def read_profile(path: Path, lo: float, hi: float):
    """Every host span named ``bench.*``, ``engine.*`` or ``trainer.*``;
    the stats of each device plane, and per line of it, its events in
    [lo, hi], the union of their intervals and the stats of its first
    Pallas event."""
    from jax.profiler import ProfileData

    host, lines = [], {}
    for plane in ProfileData.from_file(str(path)).planes:
        device = T.DEVICE_PLANE.match(plane.name)
        if device:
            lines[plane.name] = [[k, str(v)[:200]] for k, v in plane.stats]
        for line in plane.lines:
            iv, pallas = [], None
            for ev in line.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                if not device:
                    if ev.name.startswith(("bench.", *PROGRAM)):
                        host.append((ev.name, a, b))
                elif min(b, hi) > max(a, lo):
                    iv.append((max(a, lo), min(b, hi)))
                    if pallas is None and T.is_pallas(ev.name):
                        pallas = {"name": ev.name[:400],
                                  "stats": [[k, str(v)[:200]] for k, v in ev.stats]}
            if device:
                lines[f"{plane.name} {line.name}"] = {
                    "events": len(iv), "union_s": T.union_length(iv), "pallas": pallas}
    return host, lines


def sample_slice(device_ops: Dict[str, List], host: List[Span], lo: float, hi: float) -> Dict:
    """[lo, hi] of a trace, compact: instruction names in one table, times
    in whole nanoseconds from ``lo``; :func:`load_slice` reads it back."""
    names: Dict[str, int] = {}

    def ns(t):
        return round((t - lo) * 1e9)

    def short(name):
        head = name.split(" = ", 1)[0]
        return head + ' = custom_call_target="tpu_custom_call"' if T.is_pallas(name) else head

    ops = {d: [[names.setdefault(short(n), len(names)), ns(a), ns(b)]
               for n, a, b in v if b > lo and a < hi] for d, v in device_ops.items()}
    return {"window_s": hi - lo, "names": list(names), "device_ops": ops,
            "host_spans": [[n, ns(a), ns(b)] for n, a, b in host
                           if n != "bench.window" and b > lo and a < hi]}


def load_slice(data: Dict):
    """(device ops, host spans with ``bench.window``) in seconds."""
    names = data["names"]
    ops = {d: [(names[i], a * 1e-9, b * 1e-9) for i, a, b in v]
           for d, v in data["device_ops"].items()}
    host = [("bench.window", 0.0, data["window_s"])]
    return ops, host + [(n, a * 1e-9, b * 1e-9) for n, a, b in data["host_spans"]]


def span_cost_us(n: int = 100_000) -> float:
    """Host cost of one span with the profiler off, in microseconds."""
    from repro.runtime.spans import Spans

    sp = Spans()
    t0 = time.perf_counter()
    for _ in range(n):
        with sp.span("cost"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def traced_run(run, bench: Dict, cell: Dict, cfg: Dict, traffic: Dict, seed: int,
               seconds: float, scratch: Path = None, sample: Path = None) -> Dict:
    """Run the cell's job in this process with the window traced (``run``
    is ``bench/run.py``, loaded), and reduce its trace."""
    from peaks import peaks_for
    import jax
    from repro.runtime import spans as spans_lib

    owners = []
    init = spans_lib.Spans.__init__

    def keep(self):  # the job's engine or trainer makes its table here
        init(self)
        owners.append(self)

    class Ctx(run.Context):
        def start_window(self):
            self.before = [s.snapshot() for s in owners]
            super().start_window()

        def stop_window(self):
            super().stop_window()
            self.after = [s.snapshot() for s in owners]

    ctx = Ctx(cell, cfg, traffic, seed, seconds, True, scratch=scratch)
    spans_lib.Spans.__init__ = keep
    try:
        numbers = run.job_for(traffic)(ctx)
    finally:
        spans_lib.Spans.__init__ = init
    path = sorted(ctx.trace_dir.glob("**/*.xplane.pb"))[-1]
    device_ops, bench_spans, _ = T.read_xplane(path)
    red = T.reduce_events(device_ops, bench_spans)
    lo = next(a for n, a, _ in bench_spans if n == "bench.window")
    host, lines = read_profile(path, lo, lo + red["window_s"])
    mine = reduce_spans(device_ops, host)
    moved = [spans_lib.delta(a, b) for a, b in zip(ctx.before, ctx.after)]
    delta = next((d for d in moved if d["spans"]), {"spans": {}, "counts": {}})
    rec = run.Record(ctx, peaks_for(jax.devices()[0].device_kind), red)
    metrics = {m["name"]: run.load_reader(m["name"])(rec)
               for m in run.cell_metrics(bench, cell["name"], per_layer=True)}
    if "engine_steps" in ctx.counters:
        metrics.update(serve_readings(delta["spans"], ctx.counters["engine_steps"]))
    metrics["host_idle_share"] = 100.0 * mine["host_idle_s"] / red["window_s"]
    if sample is not None:
        sample.write_text(json.dumps(sample_slice(device_ops, host, lo, lo + 0.4)))
    return {
        "cell": cell["name"], "seed": seed, "numbers": numbers, "e2e": ctx.e2e,
        "setup": ctx.setup, "metrics": metrics,
        "window_s": red["window_s"], "busy_s": red["busy_s"], **mine,
        "breakdown": red["breakdown"], "spans": delta,
        "counters": {k: v for k, v in ctx.counters.items() if not isinstance(v, list)},
        "device_lines": lines,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path)
    ap.add_argument("--sample", type=Path)
    args = ap.parse_args(argv)

    run = _load("run")
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(run.ROOT / "src"))
    bench, cell, cfg, traffic, _ = run.resolve(args.workload)
    import jax

    from repro.launch import compile_cache

    if jax.devices()[0].platform != "tpu":
        print("span_trace: no TPU; nothing was run", file=sys.stderr)
        return 1
    compile_cache.enable()
    out = dict(span_cost_us=span_cost_us(),
               **traced_run(run, bench, cell, cfg, traffic, args.seed, args.seconds,
                            sample=args.sample))
    text = json.dumps(out)
    if args.out:
        args.out.write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
