"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` (nothing but JAX).  Device planes are those
named ``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event
per operation that ran.  Host spans are the harness's own
``TraceAnnotation`` events, named ``bench.*``; the span ``bench.window``
bounds the measured window.

* busy: the union of the op intervals of a device inside the window,
  averaged over the devices;
* Pallas events: ops whose name is a Pallas kernel's, by the rule in
  :func:`is_pallas` (the program's kernels carry no names of their own);
* breakdown: the ten ops that took most device time, and the ten longest
  idle gaps inside the window, each named by the innermost ``bench.*``
  host span open at the gap's middle (or "no span").
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def is_pallas(name: str) -> bool:
    """A Pallas kernel's event.  On a TPU the ``XLA Ops`` events are named
    by their HLO instruction, and a Pallas (Mosaic) kernel is a custom
    call to ``tpu_custom_call``; the program's kernels carry no names of
    their own yet, so they cannot be told apart (PERF.md §5)."""
    return 'custom_call_target="tpu_custom_call"' in name


def short_name(name: str) -> str:
    """An op event's HLO instruction name (``fusion.12``), marked when it
    is a Pallas kernel; the event's full name is the instruction text."""
    head = name.split(" = ", 1)[0].lstrip("%")[:64]
    return head + " (pallas)" if is_pallas(name) else head


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """Idle intervals of [lo, hi] not covered by ``intervals``."""
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_spans: List[Tuple[str, float, float]]) -> Dict:
    """``device_ops``: device -> [(op name, start s, end s)];
    ``host_spans``: [(name, start s, end s)] of ``bench.*`` spans."""
    win = [s for s in host_spans if s[0] == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0][1], win[0][2]
    window_s = hi - lo
    busy, pallas, per_op = [], 0.0, {}
    idle: List[Tuple[float, float]] = []
    for dev, ops in sorted(device_ops.items()):
        iv = []
        for name, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            iv.append((a, b))
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + (b - a)
            if is_pallas(name):
                pallas += b - a
        busy.append(union_length(iv))
        idle += gaps(iv, lo, hi)
    n_dev = max(len(device_ops), 1)
    spans = sorted((s for s in host_spans if s[0] != "bench.window"),
                   key=lambda s: s[1])

    def span_at(t):
        best = None
        for name, a, b in spans:
            if a <= t <= b and (best is None or b - a < best[2] - best[1]):
                best = (name, a, b)
        return best[0] if best else "no span"

    top_gaps = sorted(idle, key=lambda g: g[1] - g[0], reverse=True)[:10]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "pallas_s": pallas / n_dev,
        "op_s": {k: v / n_dev for k, v in per_op.items()},
        "pallas_ops": sorted(k for k in per_op if k.endswith("(pallas)"))[:20],
        "breakdown": {
            "device_ops": [[k, v / n_dev] for k, v in
                           sorted(per_op.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[span_at((a + b) / 2), b - a] for a, b in top_gaps],
        },
    }


def read_xplane(path: Path):
    """(device ops, bench.* host spans, line names of each device plane)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device_ops: Dict[str, List] = {}
    host: List = []
    lines: Dict[str, List[str]] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device_ops.setdefault(plane.name, [])
            lines[plane.name] = [line.name for line in plane.lines]
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    ops.append((ev.name, a, a + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        a = ev.start_ns * 1e-9
                        host.append((ev.name, a, a + ev.duration_ns * 1e-9))
    return device_ops, host, lines


def reduce_dir(trace_dir: Path) -> Dict:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    device_ops, host, lines = read_xplane(files[-1])
    return dict(reduce_events(device_ops, host), lines=lines)
