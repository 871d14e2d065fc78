"""Perf hillclimbing driver (EXPERIMENTS.md §Perf).

Re-lowers one dry-run cell with a named variant (sharding / memory-policy
/ model-layout change), prints the three roofline terms next to the
baseline, and appends the iteration to results/perf_log.json.

  PYTHONPATH=src python -m benchmarks.hillclimb --arch mamba2-130m \\
      --shape train_4k --variant microbatches=4,remat=full

Variants are comma-separated key=value tcfg overrides, plus special keys:
  shard_fallback=1   REPRO_SHARD_FALLBACK (K-dim TP fallback for
                     non-divisible projection outputs)
  approx=<mode>      exact | inject | model (train cells)
"""
from __future__ import annotations

import argparse
import json
import os


def parse_variant(s: str):
    tcfg, env, approx = {}, {}, "inject"
    if not s:
        return tcfg, env, approx
    for kv in s.split(","):
        k, _, v = kv.partition("=")
        if k == "shard_fallback":
            env["REPRO_SHARD_FALLBACK"] = v
        elif k == "moe_groups":
            env["REPRO_MOE_GROUPS"] = v
        elif k == "ssm_pad":
            env["REPRO_SSM_PAD"] = v
        elif k == "pad_vocab":
            env["REPRO_PAD_VOCAB"] = v
        elif k == "embed_replicated":
            env["REPRO_EMBED_REPLICATED"] = v
        elif k == "approx":
            approx = v
        elif k in ("microbatches", "chunk_q"):
            tcfg[k] = int(v)
        elif k in ("fsdp", "seq_shard"):
            key = "seq_shard_activations" if k == "seq_shard" else k
            tcfg[key] = v in ("1", "true", "True")
        elif k == "remat":
            tcfg[k] = v
        else:
            raise ValueError(f"unknown variant key {k}")
    return tcfg, env, approx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="")
    ap.add_argument("--note", default="")
    ap.add_argument("--baseline", default="results/dryrun_single.json")
    ap.add_argument("--log", default="results/perf_log.json")
    args = ap.parse_args()

    tcfg_over, env, approx = parse_variant(args.variant)
    os.environ.update(env)

    # import AFTER env so the sharding-rule toggles are seen
    from repro.configs import get_config, shapes_for
    from repro.launch.dryrun import force_host_devices, run_cell

    force_host_devices()

    cfg = get_config(args.arch)
    shape = next(s for s in shapes_for(cfg) if s.name == args.shape)
    res = run_cell(args.arch, shape, multi_pod=False, approx_mode=approx, **tcfg_over)

    base = None
    if os.path.exists(args.baseline):
        for r in json.load(open(args.baseline)):
            if r["arch"] == args.arch and r["shape"] == args.shape and r["mesh"] == "16x16":
                base = r
                break

    def fmt(r):
        rl = r["roofline"] if isinstance(r, dict) else r.roofline
        mem = r["memory"] if isinstance(r, dict) else r.memory
        return {
            "compute_s": rl["compute_s"], "memory_s": rl["memory_s"],
            "collective_s": rl["collective_s"], "dominant": rl["dominant"],
            "useful": rl["model_flops_ratio"],
            "temp_gib": (mem or {}).get("temp_size_in_bytes", 0) / 2**30,
            "args_gib": (mem or {}).get("argument_size_in_bytes", 0) / 2**30,
        }

    import dataclasses
    out = {
        "arch": args.arch, "shape": args.shape, "variant": args.variant,
        "note": args.note, "result": dataclasses.asdict(res),
    }
    print("\n=== variant:", args.variant or "(baseline re-run)")
    if not res.ok:
        print("FAILED:", res.error)
    else:
        v = fmt(dataclasses.asdict(res))
        print("variant :", json.dumps(v, default=float))
        if base and base.get("ok"):
            b = fmt(base)
            print("baseline:", json.dumps(b, default=float))
            for k in ("compute_s", "memory_s", "collective_s", "temp_gib"):
                if b[k]:
                    print(f"  {k}: {b[k]:.4g} -> {v[k]:.4g}  ({v[k]/b[k]*100-100:+.1f}%)")
    log = []
    if os.path.exists(args.log):
        log = json.load(open(args.log))
    log.append(out)
    with open(args.log, "w") as f:
        json.dump(log, f, indent=1, default=float)


if __name__ == "__main__":
    main()
