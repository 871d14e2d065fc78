"""Serving job: the program's continuous-batching ``Engine`` under a
traffic file's requests.

Set-up makes the weights in one jitted call from the seed, builds the
engine with the fused decode path, and serves one request per prefill
bucket the traffic's lengths can reach (compiling or loading the
bucket's prefill, the decode step and the slot reset).  The window then
submits each request when it is due and steps the engine until
``--seconds`` have passed; every token's time comes from the engine's
stream callback.  After the window a sample of finished requests, drawn
from the seed and holding the longest, is run through the plain
reference, teacher-forced on its prompt and served tokens.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import traffic_gen
import weights as W
from flops import dims
from program import approx_config, model_config, program_seed


def run(ctx) -> Dict:
    from repro.models import build_model
    from repro.runtime.engine import Engine, Request

    cfg, t, seed = ctx.cfg, ctx.traffic, ctx.seed
    dep = cfg["deployment"]
    n_slots, max_seq = int(dep["n_slots"]), int(dep["max_seq"])
    vocab = dims(cfg)["v"]
    model = build_model(model_config(cfg))
    base = approx_config(dict(t, backend="exact", mode="no_model"))

    t_init = time.perf_counter()
    params = W.params_from_seed(cfg, seed)
    jax.block_until_ready(params)
    ctx.setup["init_s"] = time.perf_counter() - t_init

    times: Dict[int, List[float]] = {}

    def stream(rid, token, done):
        times.setdefault(rid, []).append(time.perf_counter())

    engine = Engine(model, params, n_slots=n_slots, max_seq=max_seq,
                    approx_base=base, seed=program_seed(seed), fused=True,
                    stream=stream)
    backend = t["backend"]
    reqs = traffic_gen.requests(t, vocab, seed)
    plen = {r["rid"]: len(r["prompt"]) for r in reqs}

    # warm-up: one request per prefill bucket the traffic reaches
    t_warm = time.perf_counter()
    buckets = sorted({engine._bucket(len(r["prompt"])) for r in reqs})
    wrng = np.random.default_rng([seed, 7])
    warm = [Request(rid=-(i + 1), prompt=tuple(wrng.integers(0, vocab, min(b, max_seq - 2))),
                    max_new_tokens=2, backend=backend)
            for i, b in enumerate(buckets)]
    engine.run(warm)
    ctx.setup["warm_s"] = time.perf_counter() - t_warm
    ctx.setup["compile_s"] = engine.compile_s
    ctx.counters["buckets"] = buckets
    times.clear()

    prepared = [Request(rid=r["rid"], prompt=tuple(r["prompt"].tolist()),
                        max_new_tokens=r["max_new_tokens"], backend=backend)
                for r in reqs]
    before = (engine.prefill_s, engine.decode_s, engine.decode_steps,
              engine.compile_s, len(engine.compiles))
    lateness: List[float] = []
    bucket_calls: List[int] = []
    nxt = 0
    ctx.start_window()
    t0 = time.perf_counter()
    steps = 0
    while True:
        now = time.perf_counter()
        if now - t0 >= ctx.seconds:
            break
        with jax.profiler.TraceAnnotation("bench.submit"):
            while nxt < len(reqs) and reqs[nxt]["due_s"] <= now - t0:
                engine.submit(prepared[nxt])
                lateness.append(now - t0 - reqs[nxt]["due_s"])
                nxt += 1
        busy = engine.pending or any(l.n_active() for l in engine.lanes.values())
        if not busy:
            wait = reqs[nxt]["due_s"] - (time.perf_counter() - t0) if nxt < len(reqs) else 0.01
            time.sleep(max(0.0, min(wait, ctx.seconds - (time.perf_counter() - t0))))
            continue
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            engine.step()
        steps += 1
    t1 = time.perf_counter()
    ctx.stop_window()
    ctx.memory_peak()

    window = t1 - t0
    in_window = {rid: [x for x in ts if t0 <= x <= t1] for rid, ts in times.items()}
    n_tokens = sum(len(v) for v in in_window.values())
    gaps = [b - a for ts in in_window.values() for a, b in zip(ts, ts[1:])]
    firsts = {rid: ts[0] for rid, ts in times.items() if ts}
    ttft = []
    for r in reqs[:nxt]:
        due = t0 + r["due_s"]
        ttft.append((firsts[r["rid"]] if r["rid"] in firsts else t1) - due)
    ctx.e2e["serve_tok_s"] = n_tokens / window
    ctx.e2e["itl_p95_ms"] = traffic_gen.percentile(gaps, 95) * 1e3
    ctx.e2e["ttft_p95_ms"] = traffic_gen.percentile(ttft, 95) * 1e3
    # decode rows read the keys and values of positions 0..prompt+k-1
    contexts = [plen[rid] + k for rid, ts in times.items() for k in range(1, len(ts))]
    for rid in firsts:
        bucket_calls.append(engine._bucket(plen[rid]))
    ctx.counters.update(
        window_s=window, engine_steps=steps, output_tokens=n_tokens,
        prefill_s=engine.prefill_s - before[0], decode_s=engine.decode_s - before[1],
        decode_steps=engine.decode_steps - before[2],
        compiles_in_window=len(engine.compiles) - before[4],
        prefill_tokens=sum(plen[rid] for rid in firsts),
        prefill_lengths=[plen[rid] for rid in firsts],
        prefill_buckets=bucket_calls, decode_contexts=contexts,
        n_slots=n_slots, itl_samples=len(gaps), ttft_samples=len(ttft),
        generator_late_p99_ms=traffic_gen.percentile(lateness, 99) * 1e3 if lateness else 0.0,
        submitted=nxt,
    )
    done = {rid: res["tokens"] for rid, res in engine.results.items() if rid >= 0}
    ctx.attempted, ctx.failed = len(firsts), 0
    del engine, params
    gc.collect()

    sample = sample_requests(done, plen, int(t["check_requests"]), seed)
    by_rid = {r["rid"]: r for r in reqs}
    items = [(by_rid[rid]["prompt"], done[rid]) for rid in sample]
    ctx.counters["checked_requests"] = len(items)
    ctx.counters["checked_tokens"] = sum(len(s) for _, s in items)
    t_ref = time.perf_counter()
    casts = {"reference": R.no_cast}
    if ctx.control:
        casts["fp8"] = R.fp8_cast
    gaps_ = served_gaps(cfg, t, seed, items, casts)
    ctx.counters["reference_s"] = time.perf_counter() - t_ref
    if ctx.control:
        ctx.control_numbers["fp8"] = {"logit_gap": gaps_["fp8"]}
    return {"logit_gap": gaps_["served"]}


def sample_requests(done: Dict[int, List[int]], plen: Dict[int, int], n: int,
                    seed: int) -> List[int]:
    """The finished request with the most tokens, and others drawn from
    the seed, ``n`` in all."""
    if not done:
        return []
    rids = sorted(done)
    longest = max(rids, key=lambda r: (plen[r] + len(done[r]), r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([seed, 29])
    pick = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False)) if rest else []
    return [longest] + [int(r) for r in pick]


def served_gaps(cfg: Dict, t: Dict, seed: int, items, casts: Dict) -> Dict[str, float]:
    """Teacher-forced reference over each (prompt, served tokens): the
    widest gap by which a served token's reference logit lies below the
    reference's best at its position ("served").  For each further cast
    (a control), the same gap of the token that cast puts first."""
    m = dims(cfg)
    L = m["layers"]
    if not items:
        return {"served": float("inf"), **{k: float("inf") for k in casts if k != "reference"}}
    products = {k: R.product_fn(t, c) for k, c in casts.items()}
    key = W.seed_key(seed)
    glob = jax.jit(lambda k: W.make_globals(cfg, k))(key)
    emb = glob["embed"]["tok"]
    seqs = []
    for prompt, served in items:
        toks = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
        pad = 1 << max(6, int(np.ceil(np.log2(len(toks)))))
        padded = np.zeros(pad, np.int32)
        padded[: len(toks)] = toks
        seqs.append((padded, len(prompt), np.asarray(served, np.int32)))
    hidden = {k: [jnp.asarray(emb, jnp.float32)[s[0]][None] for s in seqs] for k in casts}
    layer_jit = {
        k: jax.jit(lambda x, p, k=k: R.layer_forward(x, p, cfg, products[k], {}, casts[k]))
        for k in casts
    }
    make_layer = jax.jit(lambda k, l: W.make_layer(cfg, k, l))
    with jax.default_matmul_precision("highest"):
        for l in range(L):
            p = make_layer(key, l)
            for k in casts:
                hidden[k] = [layer_jit[k](x, p) for x in hidden[k]]
            del p
        head = jnp.asarray(R.head_weight(glob, cfg))
        fnorm = glob["final_norm"].astype(jnp.float32)
        eps = float(cfg["rms_norm_eps"])

        worst = {"served": 0.0, **{k: 0.0 for k in casts if k != "reference"}}
        # weights are arguments, never constants baked into the program
        heads = {k: jax.jit(lambda x, rows, fnorm, head, k=k: products[k](
            R.rmsnorm(x[0][rows], fnorm, eps), head, None)) for k in casts}
        for i, (padded, P, served) in enumerate(seqs):
            n = len(served)
            # rows padded to a power of two (one compile per size); the
            # per-row quantization keeps each row's logits its own
            rows = np.full(1 << max(4, int(np.ceil(np.log2(n)))), P - 1)
            rows[:n] = P - 1 + np.arange(n)
            ref = np.asarray(heads["reference"](hidden["reference"][i], rows, fnorm, head))[:n]
            best = ref.max(axis=-1)
            got = ref[np.arange(n), served]
            worst["served"] = max(worst["served"], float((best - got).max()))
            for k in casts:
                if k == "reference":
                    continue
                low = np.asarray(heads[k](hidden[k][i], rows, fnorm, head))[:n]
                pick = low.argmax(axis=-1)
                worst[k] = max(worst[k], float((best - ref[np.arange(n), pick]).max()))
    return worst
