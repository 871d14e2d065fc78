#!/usr/bin/env python3
"""Drive the main path once on one TPU chip and check what comes out.

Two phases, in one process (a chip belongs to one process at a time):

* **serve** — ``qwen2.5-3b`` at its published widths and all 36 layers,
  random bf16 weights from ``--seed``, through the continuous-batching
  ``Engine`` with the fused decode path.  A mixed queue cycles the
  requests over every backend.  Every request must finish its token
  budget with finite logits; the greedy tokens of the exact requests must
  agree with a plain ``model.apply`` forward; one emulated request is
  replayed on the composed (unfused) oracle and the logit gap printed.
* **train** — the same widths with the depth cut, through ``Trainer``:
  exact, then error injection with calibration, then bit-accurate MODEL
  fine-tuning, under stochastic computing.  Losses must stay finite.

The last line of standard output is one JSON object, printed only on a
chip and only when every check passed::

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without a TPU the script exits non-zero before any work.
``--rehearse`` runs both phases on the CPU at the smoke-size config, with
whatever kernels ``REPRO_KERNELS`` selects, and never prints ``"ok"``.

  python chip_smoke.py              # on a machine with one TPU chip
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_DIR = ROOT / ".smoke_ckpt"
BACKENDS = ("exact", "sc", "analog", "approx_mult", "log_mult")
SLOTS = 4
# training cut: depth only; 2 of 36 layers with sm3 optimizer state and
# the state donated to each step keeps the MODEL step at 13.1 GB in the
# compiler's memory analysis for a v5e (16 GiB), batch 2 x 256 tokens
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, OPTIM_COMPRESS = 2, 2, 256, "sm3"


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"check passed: {what}")


def _peak_hbm(jax) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log("peak device memory so far: "
        + (f"{peak} bytes" if peak is not None else "not reported"))


def _graph_label(key) -> str:
    """A readable name for one of the Engine's compiled-graph keys."""
    from repro.configs.base import ApproxConfig

    approx = next((k for k in key if isinstance(k, ApproxConfig)), None)
    active = approx is not None and approx.active
    label = f"{key[0]}/{approx.backend.value if active else 'exact'}"
    if key[0].startswith("prefill"):
        label += f"/bucket{key[3]}"
    return label


def _exact_agreement(forward, params, req, result, max_seq):
    """Teacher-forced check of one exact request's greedy tokens against a
    plain full-sequence forward (``forward(params, tokens[1, max_seq])``
    -> logits).  Returns (positions, identical argmax, worst unexplained
    gap, worst logit difference)."""
    import numpy as np

    P = len(req.prompt)
    toks = list(req.prompt) + result["tokens"][:-1]
    seq = np.zeros((1, max_seq), np.int32)
    seq[0, : len(toks)] = toks
    ref = np.asarray(forward(params, seq)[0], np.float32)
    same = 0
    worst_gap = 0.0
    worst_diff = 0.0
    for j, tok in enumerate(result["tokens"]):
        row = ref[P - 1 + j]
        mine = np.asarray(result["logits"][j], np.float32)
        diff = float(np.abs(row - mine).max())
        worst_diff = max(worst_diff, diff)
        if int(row.argmax()) == tok:
            same += 1
            continue
        # a different argmax is a near-tie only if the two logits of the
        # reference sit closer than the two paths' measured disagreement
        worst_gap = max(worst_gap, float(row.max() - row[tok]) - 2 * diff)
    return len(result["tokens"]), same, worst_gap, worst_diff


def serve_phase(args, jax, on_chip: bool) -> None:
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.configs.base import ApproxConfig
    from repro.models import build_model
    from repro.runtime.engine import Engine, synthetic_requests

    cfg = (get_smoke_config if args.rehearse else get_config)("qwen2.5-3b")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"serve: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {n_bytes} bytes of "
        f"{cfg.param_dtype} weights, init {time.perf_counter() - t0:.1f} s")

    if args.rehearse:
        prompt_lens, gen_lens, max_seq = (8, 24), (4, 8), 64
    else:
        prompt_lens, gen_lens, max_seq = (64, 256), (16, 32), 512
    queue = synthetic_requests(
        args.requests, cfg.vocab_size, seed=args.seed,
        prompt_lens=prompt_lens, gen_lens=gen_lens, backends=BACKENDS,
    )
    log(f"serve: {len(queue)} requests, prompts {prompt_lens}, generated "
        f"{gen_lens}, {SLOTS} slots, max_seq {max_seq}, one lane per "
        f"backend (no switch dispatch), fused decode")
    engine = Engine(
        model, params, n_slots=SLOTS, max_seq=max_seq,
        approx_base=ApproxConfig(), seed=args.seed, fused=True,
        collect_logits=True,
    )
    t0 = time.perf_counter()
    results = engine.run(queue)
    log(f"serve: queue done in {time.perf_counter() - t0:.1f} s")
    for key, sec in engine.compiles:
        log(f"serve: compile {_graph_label(key)}: {sec:.2f} s (first call)")
    log(f"serve: compile total {engine.compile_s:.2f} s")
    m = engine.metrics()
    log(f"serve: steady-state prefill {m['prefill_tokens']} tokens in "
        f"{m['prefill_s']:.3f} s, decode {m['decode_tokens']} tokens in "
        f"{m['decode_s']:.3f} s")

    per_backend = {}
    for req in queue:
        res = results.get(req.rid)
        check(res is not None and len(res["tokens"]) == req.max_new_tokens,
              f"request {req.rid} ({req.backend}) generated its "
              f"{req.max_new_tokens} tokens")
        rows = np.stack([np.asarray(r, np.float32) for r in res["logits"]])
        check(bool(np.isfinite(rows).all()),
              f"request {req.rid} ({req.backend}) logits are finite")
        per_backend[req.backend] = per_backend.get(req.backend, 0) + len(res["tokens"])
    log(f"serve: tokens per backend {json.dumps(per_backend)}")

    # right padding leaves a causal forward's logits at the real
    # positions unchanged, so one compiled shape serves every request
    forward = jax.jit(
        lambda p, t: model.apply(p, {"tokens": t}, remat="none").logits
    )
    for req in queue:
        if req.backend != "exact":
            continue
        n, same, gap, diff = _exact_agreement(
            forward, params, req, results[req.rid], max_seq
        )
        log(f"serve: exact request {req.rid}: {same}/{n} greedy tokens equal "
            f"the model.apply argmax; max |engine - forward| logit {diff:.4f}")
        check(gap <= 0.0,
              f"exact request {req.rid} greedy tokens agree with model.apply "
              f"(every differing argmax is a near-tie)")

    # fused vs composed: replay one deterministic emulated request on the
    # composed oracle, sharing the compiled prefill graphs
    probe = next(r for r in queue if r.backend == "log_mult")
    composed = Engine(
        model, params, n_slots=SLOTS, max_seq=max_seq,
        approx_base=ApproxConfig(), seed=args.seed, fused=False,
        collect_logits=True, fns=engine.fns,
    )
    other = composed.run([probe])[probe.rid]
    mine = results[probe.rid]
    worst, steps = 0.0, 0
    for j, (a, b) in enumerate(zip(mine["logits"], other["logits"])):
        worst = max(worst, float(np.abs(
            np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()))
        steps += 1
        if mine["tokens"][j] != other["tokens"][j]:
            break  # later rows are conditioned on different tokens
    log(f"serve: fused vs composed, request {probe.rid} (log_mult): max "
        f"|fused - composed| logit {worst:.6f} over {steps} steps")

    if on_chip:
        from repro.kernels import ops as kops

        check(kops._impl() == "pallas" and not kops._interpret(),
              "kernels run compiled Pallas (no jnp reference, no interpreter)")
        lanes = [l for l in engine.lanes.values() if l.approx.active]
        check(bool(lanes), "emulated lanes were served")
        for lane in lanes:
            key, fn = engine._decode_key_fn(lane.approx)
            shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype),
                (engine.params, lane.cache, lane.tokens, lane.pos,
                 jax.random.PRNGKey(0)),
            )
            text = fn.lower(*shapes).as_text()
            check("tpu_custom_call" in text,
                  f"fused decode graph {_graph_label(key)} calls Pallas kernels")
    _peak_hbm(jax)


def train_phase(args, jax) -> None:
    from repro.configs import get_config, get_smoke_config
    from repro.configs.base import (
        AnalogParams, ApproxConfig, Backend, Phase, TrainConfig, TrainMode,
    )
    from repro.data import SyntheticLM
    from repro.hw import VariationModel
    from repro.models import build_model
    from repro.runtime.trainer import Trainer

    full = (get_smoke_config if args.rehearse else get_config)("qwen2.5-3b")
    cfg = dataclasses.replace(full, n_layers=min(TRAIN_LAYERS, full.n_layers))
    batch, seq = (2, 32) if args.rehearse else (TRAIN_BATCH, TRAIN_SEQ)
    log(f"train: {cfg.name} cut to {cfg.n_layers} of {full.n_layers} layers "
        f"(widths unchanged), batch {batch} x seq {seq}, optimizer state "
        f"{OPTIM_COMPRESS}")
    model = build_model(cfg)
    approx = ApproxConfig(
        backend=Backend.SC, mode=TrainMode.INJECT,
        analog=AnalogParams(array_size=min(128, cfg.d_model)),
    )
    phases = (Phase.exact(2), Phase.inject(2), Phase.model(2))
    total = sum(p.steps for p in phases)
    tcfg = TrainConfig(
        learning_rate=1e-3, total_steps=total, warmup_steps=1, phases=phases,
        checkpoint_every=total, optim_compress=OPTIM_COMPRESS,
    )
    data = SyntheticLM(
        cfg.vocab_size, seq, batch, seed=args.seed,
        frontend_tokens=cfg.frontend_tokens, d_model=cfg.d_model,
    )
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    trainer = Trainer(
        model, approx, tcfg, data, str(CKPT_DIR), seed=args.seed,
        log_every=1, variation=VariationModel(), restart_budget=0,
    )
    report = trainer.run(total)
    log(f"train: schedule {trainer.plan.describe()}")
    log(f"train: losses {json.dumps(report.losses)}")
    log(f"train: step seconds (first of each phase compiles) "
        f"{json.dumps([round(t, 3) for t in report.step_times])}")
    log(f"train: calibrations {report.calibrations}, mode steps "
        f"{json.dumps(report.mode_steps)}")
    check(len(report.losses) == total, f"all {total} train steps ran")
    check(all(l == l and abs(l) != float("inf") for l in report.losses),
          "train losses are finite")
    check(report.restarts == 0, "no step was replayed")
    _peak_hbm(jax)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the smoke-size config; never "
                         "reports ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=10)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from the "
              "root of a checkout", file=sys.stderr)
        return 2

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device {json.dumps(device)}")
    on_chip = not args.rehearse
    if on_chip and dev.platform != "tpu":
        print("chip_smoke: no TPU found; nothing was run (use --rehearse "
              "for the CPU rehearsal)", file=sys.stderr)
        return 1
    if on_chip:
        log(f"compile cache: {compile_cache.enable()}")

    try:
        t0 = time.perf_counter()
        serve_phase(args, jax, on_chip)
        log(f"serve phase: {time.perf_counter() - t0:.1f} s")
        gc.collect()
        t0 = time.perf_counter()
        train_phase(args, jax)
        log(f"train phase: {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    if on_chip:
        print(json.dumps({"ok": True, "device": device}))
    else:
        log("rehearsal passed (no result is reported off the chip)")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    sys.exit(main())
