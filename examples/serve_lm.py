"""Mixed-backend serving demo: one engine, four hardware targets.

Serves a small queue where each request is deployed on different
approximate hardware — exact, Mitchell log-mult, stochastic computing,
and an AxTrain-style mixed-site request (SC attention + log-mult FFN) —
side by side in one continuous-batching engine.  Non-exact requests get
bit-accurate MODEL-mode emulated logits (what their hardware would
produce), streamed as they decode.

  PYTHONPATH=src python examples/serve_lm.py --arch qwen2.5-3b
"""
import argparse
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.runtime.engine import Engine, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused", action="store_true", default=None,
                    help="fused decode hot path (default: REPRO_FUSED env)")
    ap.add_argument("--no-fused", dest="fused", action="store_false")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    prompt = tuple(
        int(t)
        for t in jax.random.randint(
            jax.random.fold_in(rng, 1), (9,), 0, cfg.vocab_size
        )
    )

    queue = [
        Request(rid=0, prompt=prompt, max_new_tokens=args.gen, backend="exact"),
        Request(rid=1, prompt=prompt, max_new_tokens=args.gen, backend="log_mult"),
        Request(rid=2, prompt=prompt[:5], max_new_tokens=args.gen, backend="sc"),
        Request(
            rid=3,
            prompt=prompt[:7],
            max_new_tokens=args.gen,
            site_backends=(("attn_*", "sc"), ("mlp_*", "log_mult")),
        ),
    ]

    def stream(rid, tok, done):
        print(f"  rid={rid} tok={tok}{'  <done>' if done else ''}")

    max_seq = max(len(r.prompt) + r.max_new_tokens for r in queue)
    engine = Engine(
        model, params, n_slots=args.slots, max_seq=max_seq, seed=args.seed,
        stream=stream, fused=args.fused,
    )
    results = engine.run(queue)

    print()
    for req in queue:
        r = results[req.rid]
        hw = req.backend if not req.site_backends else (
            "+".join(sorted({n for _, n in req.site_backends})) + " (mixed-site)"
        )
        tag = "MODEL-emulated" if r["emulated"] else "exact"
        print(f"request {req.rid} [{hw}, {tag}]: {r['tokens']}")
    m = engine.metrics()
    dec = m["spans"].get("engine.decode", {"count": 0, "total_s": 0.0})
    # decode tok/s is steady-state: the engine keeps compiling calls out
    # of the decode clock, so fused-vs-composed runs compare cleanly
    print(
        f"\n{m['requests']} requests over {m['lanes']} lanes | "
        f"decode {m['decode_tok_s']:.0f} tok/s "
        f"({'fused' if m['fused'] else 'composed'} path, compile excluded) | "
        f"decode call {1e3 * dec['total_s'] / max(dec['count'], 1):.2f} ms | "
        f"compile {m['compile_s']:.1f} s"
    )


if __name__ == "__main__":
    main()
