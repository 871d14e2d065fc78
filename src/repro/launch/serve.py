"""Serving driver: continuous-batching engine over a synthetic request queue.

Thin CLI over :class:`repro.runtime.engine.Engine`: builds a queue of
synthetic requests with mixed prompt/generation lengths and per-request
backends, serves it with continuous batching (slot admit/evict, bucketed
bulk prefill, one compiled decode step per serving config), and reports
prefill/decode/total tok/s, the engine's span table (count, total and
self seconds per span), slot utilization, and compile time (reported
separately — it never pollutes the steady-state throughput numbers).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \\
      --requests 12 --slots 4 --prompt-len 16 --gen 32 \\
      --backends exact,log_mult --out results/serve_smoke.json

``--fleet N`` binds each emulated lane to one of N sampled device
instances (chip-to-chip variation, ``repro.hw``); ``--drift`` ages them
as tokens are served, with adaptive online recalibration pulling
drifted chips back (the ``fleet`` field of the report JSON carries each
chip's probe-loss trajectory).

``--fused`` / ``--no-fused`` route decode through the fused hot path
(epilogue-fused backend kernels + flash decode attention) or force the
composed path; unset, the ``REPRO_FUSED`` env toggle decides.  Both
paths (and the static baseline) report steady-state tok/s with
compiling calls excluded, so fused-vs-composed comparisons are never
polluted by compile time.

``--static`` instead runs the pre-engine static-batch driver (waves of
padded requests) with its timing fixed — the baseline
``benchmarks/bench_serve.py`` compares against.  ``--stream`` prints
tokens as they are produced.

``--fabric --replicas N`` serves the queue through the serving fabric
(:mod:`repro.serving`): N engine replicas behind health/load-aware
admission + placement, each holding a stripe of the ``--fleet`` chips,
with drift-triggered recalibration running off the hot path in the
async recal service.  ``--router round_robin`` swaps in the
health-blind placement baseline; ``--latency-tolerant-frac`` marks that
fraction of requests as parkable on drifted chips; ``--queue-depth``
bounds each replica's inbox (admission rejects with a backpressure code
when every eligible inbox is full).  The report is ``fabric_report()``.
"""
from __future__ import annotations

import argparse
import json
import os

import dataclasses

import jax

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ApproxConfig, parse_site_backends
from repro.launch import compile_cache
from repro.models import build_model
from repro.models.transformer import ALL_SITES
from repro.runtime.engine import (
    Engine,
    run_static_baseline,
    synthetic_requests,
)


def build_queue(args, vocab_size: int, site_backends=()):
    lo_p = args.prompt_len if not args.mixed else max(2, args.prompt_len // 4)
    lo_g = args.gen if not args.mixed else max(2, args.gen // 4)
    queue = synthetic_requests(
        args.requests,
        vocab_size,
        seed=args.seed,
        prompt_lens=(lo_p, args.prompt_len),
        gen_lens=(lo_g, args.gen),
        backends=tuple(args.backends.split(",")),
        temperature=args.temperature,
    )
    if site_backends:
        # every request deploys the heterogeneous map (e.g. the spec the
        # approximation search emitted); its --backends entry still sets
        # the default backend for sites the map doesn't match
        queue = [
            dataclasses.replace(r, site_backends=site_backends) for r in queue
        ]
    return queue


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="serving window (default prompt-len + gen)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mixed", action="store_true", default=True,
                    help="mixed prompt/gen lengths (default)")
    ap.add_argument("--uniform", dest="mixed", action="store_false",
                    help="uniform prompt/gen lengths")
    ap.add_argument("--backends", default="exact",
                    help="comma list cycled over requests "
                         "(e.g. exact,log_mult,sc)")
    ap.add_argument("--site-backend", action="append", default=None,
                    metavar="PATTERN=BACKEND", dest="site_backend",
                    help="per-site backend map applied to every request "
                         "(repeatable) — e.g. the spec emitted by "
                         "python -m repro.launch.search")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve emulated requests over a fleet of N sampled "
                         "device instances (one chip per lane; chip profiles "
                         "are jit arguments, so the whole fleet shares each "
                         "backend's compiled steps)")
    ap.add_argument("--variation-scale", type=float, default=1.0,
                    help="multiplier on chip-variation sigmas (with --fleet)")
    ap.add_argument("--drift", type=float, default=0.0,
                    help="gain random-walk drift std per sqrt(kilotoken) "
                         "(0 = static chips; with --fleet)")
    ap.add_argument("--recalibrate-every", type=int, default=8,
                    help="base online-recalibration cadence in engine steps "
                         "(adaptive: halves when the probe loss drifts)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused", action="store_true", default=None,
                    help="route decode through the fused hot path "
                         "(epilogue-fused kernels + flash decode attention); "
                         "default: the REPRO_FUSED env toggle")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    help="force the composed (unfused) decode path")
    ap.add_argument("--switch", action="store_true",
                    help="one-compile heterogeneous dispatch: merge every "
                         "emulated request into one lane, per-slot backend "
                         "indices as a runtime decode argument (zero "
                         "retraces under mixed site maps); incompatible "
                         "with --fleet")
    ap.add_argument("--warm-start", action="store_true",
                    help="with --fleet: seed a newly bound chip's "
                         "correction polynomials from the fleet mean "
                         "instead of a bind-time zero-stat fit")
    ap.add_argument("--fabric", action="store_true",
                    help="serve through the fabric control plane "
                         "(repro.serving): --replicas engine replicas "
                         "behind health/load-aware routing, async "
                         "recalibration off the hot path")
    ap.add_argument("--replicas", type=int, default=2,
                    help="engine replicas (with --fabric)")
    ap.add_argument("--router", choices=("health", "round_robin"),
                    default="health",
                    help="fabric placement policy (with --fabric)")
    ap.add_argument("--latency-tolerant-frac", type=float, default=0.0,
                    help="fraction of requests marked latency_tolerant — "
                         "the router parks them on drifted chips awaiting "
                         "recalibration (with --fabric)")
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="per-replica bounded inbox (with --fabric)")
    ap.add_argument("--fabric-threads", action="store_true",
                    help="run each replica on its own thread (default: "
                         "the deterministic sync pump)")
    ap.add_argument("--static", action="store_true",
                    help="run the fixed static-batch baseline instead")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they are generated")
    ap.add_argument("--out", default="", help="write the report JSON here")
    # legacy flag of the old static driver, kept as an alias for --slots
    ap.add_argument("--batch", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args()
    compile_cache.enable()
    if args.batch:
        args.slots = args.batch

    try:
        # shared validator: typo'd patterns warn instead of silently
        # matching zero sites, unknown backends fail before any compile
        site_backends = parse_site_backends(
            args.site_backend, known_sites=ALL_SITES,
            warn=lambda m: print(f"[serve] warning: {m}"),
        )
        ApproxConfig(site_backends=site_backends)
    except ValueError as e:
        ap.error(str(e))
    if site_backends and args.static:
        ap.error("--site-backend needs the engine (the static baseline "
                 "never serves emulation); drop --static")
    if args.fleet and args.static:
        ap.error("--fleet needs the engine (the static baseline never "
                 "serves emulation); drop --static")
    if args.switch and args.static:
        ap.error("--switch needs the engine; drop --static")
    if args.switch and args.fleet:
        ap.error("--switch merges lanes across site maps, which is "
                 "incompatible with per-chip fleet lanes; drop one")
    if args.fabric and args.static:
        ap.error("--fabric routes over engine replicas (the static "
                 "baseline has no engine); drop --static")
    if args.fabric and args.switch:
        ap.error("--fabric replicas bind fleet chips per lane, which is "
                 "incompatible with --switch merged lanes; drop one")
    if args.fabric and not 0.0 <= args.latency_tolerant_frac <= 1.0:
        ap.error("--latency-tolerant-frac must be in [0, 1]")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    queue = build_queue(args, cfg.vocab_size, site_backends)
    max_seq = args.max_seq or (args.prompt_len + args.gen)

    if args.fabric:
        from repro.hw import DriftModel, Fleet, VariationModel
        from repro.serving import Fabric

        fleet = drift = None
        if args.fleet:
            fleet = Fleet(
                max(args.fleet, args.replicas), seed=args.seed + 7919,
                variation=VariationModel(scale=args.variation_scale),
            )
            if args.drift > 0:
                drift = DriftModel(
                    gain_walk_std=args.drift, offset_walk_std=args.drift / 2
                )
        if args.latency_tolerant_frac > 0:
            # every k-th request is parkable on drifted replicas
            k = max(1, round(1.0 / args.latency_tolerant_frac))
            queue = [
                dataclasses.replace(r, latency_tolerant=(i % k == 0))
                for i, r in enumerate(queue)
            ]
        fabric = Fabric(
            model, params,
            replicas=args.replicas,
            fleet=fleet, drift=drift,
            router=args.router,
            queue_depth=args.queue_depth,
            threads=args.fabric_threads,
            n_slots=args.slots, max_seq=max_seq,
            approx_base=ApproxConfig(), seed=args.seed,
            recalibrate_every=args.recalibrate_every,
            warm_start=args.warm_start,
        )
        try:
            results = fabric.run(queue)
            report = fabric.fabric_report()
        finally:
            fabric.shutdown()
        report["mode"] = "fabric"
        report["per_backend_requests"] = {}
        for r in results.values():
            report["per_backend_requests"][r["backend"]] = (
                report["per_backend_requests"].get(r["backend"], 0) + 1
            )
        if queue:
            report["sample_tokens"] = results[queue[0].rid]["tokens"][:16]
    elif args.static:
        report = run_static_baseline(model, params, queue, batch=args.slots)
        report["mode"] = "static"
        report["outputs"] = {
            rid: toks[:8] for rid, toks in report["outputs"].items()
        }
        # see run_static_baseline: shorter prompts in a mixed wave are
        # generated from the padded wave-max position
        report["outputs_note"] = (
            "static padding: outputs of shorter-prompt requests are "
            "conditioned on zero-pad context (use the engine for fidelity)"
        )
    else:
        stream = None
        if args.stream:
            stream = lambda rid, tok, done: print(
                f"  rid={rid} tok={tok}{' <done>' if done else ''}"
            )
        fleet = drift = None
        if args.fleet:
            from repro.hw import DriftModel, Fleet, VariationModel

            fleet = Fleet(
                args.fleet, seed=args.seed + 7919,
                variation=VariationModel(scale=args.variation_scale),
            )
            if args.drift > 0:
                drift = DriftModel(
                    gain_walk_std=args.drift, offset_walk_std=args.drift / 2
                )
        engine = Engine(
            model,
            params,
            n_slots=args.slots,
            max_seq=max_seq,
            approx_base=ApproxConfig(),
            seed=args.seed,
            stream=stream,
            fleet=fleet,
            drift=drift,
            recalibrate_every=args.recalibrate_every,
            fused=args.fused,
            switch=args.switch,
            warm_start=args.warm_start,
        )
        results = engine.run(queue)
        report = dict(engine.metrics())
        report["mode"] = "engine"
        if fleet is not None:
            report["fleet"] = engine.fleet_report()
        report["per_backend_requests"] = {}
        for r in results.values():
            report["per_backend_requests"][r["backend"]] = (
                report["per_backend_requests"].get(r["backend"], 0) + 1
            )
        if queue:
            report["sample_tokens"] = results[queue[0].rid]["tokens"][:16]

    report["arch"] = cfg.name
    # both drivers account identically: compiling calls run outside the
    # prefill/decode clocks and are reported as compile_s, so engine
    # fused-vs-composed (and engine-vs-static) tok/s compare cleanly
    report["timing_note"] = (
        "prefill/decode tok/s are steady-state: compiling calls are "
        "excluded from time and tokens; compile_s is reported separately"
    )
    if site_backends:
        report["site_backends"] = [f"{p}={b}" for p, b in site_backends]
    print(json.dumps(report, indent=2, default=str))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, default=str)


if __name__ == "__main__":
    main()
