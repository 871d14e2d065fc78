"""Tiny versions of the cells, for CPU tests of the harness, and the
faults those tests plant under the timed path."""
import contextlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TRAIN_CELL = "qwen2.5-3b.train.model-sc"
SERVE_CELL = "yi-6b.serve.exact-backlog"


def load(path):
    return json.loads(Path(path).read_text())


def bench():
    return load(ROOT / "BENCHMARK.json")


# the serving cell's files, also when BENCHMARK.json does not list it
SERVE_FILES = {"name": SERVE_CELL, "config": "yi-6b", "traffic": "serve.exact-backlog",
               "chips": 1, "why": "tiny serving cell for CPU tests"}


def cell(name):
    cells = {w["name"]: w for w in bench()["workloads"]}
    if name == SERVE_CELL and name not in cells:
        return SERVE_FILES
    return cells[name]


def train_parts(hidden=64, ff=96, vocab=512, layers=2, seq=32):
    c = cell(TRAIN_CELL)
    cfg = load(BENCH / "configs" / f"{c['config']}.json")
    cfg.update(hidden_size=hidden, intermediate_size=ff, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=vocab, num_hidden_layers=layers)
    traffic = load(BENCH / "traffic" / f"{c['traffic']}.json")
    traffic.update(seq=seq)
    return c, cfg, traffic


def serve_parts(**traffic_update):
    c = cell(SERVE_CELL)
    cfg = load(BENCH / "configs" / f"{c['config']}.json")
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=256, num_hidden_layers=2)
    cfg["deployment"] = dict(cfg["deployment"], n_slots=4, max_seq=128)
    traffic = load(BENCH / "traffic" / f"{c['traffic']}.json")
    traffic.update(requests=64, block=16, check_requests=4,
                   prompt_len=dict(traffic["prompt_len"], median=24, min=4, max=64),
                   output_len=dict(traffic["output_len"], median=8, min=2, max=32))
    traffic.update(traffic_update)
    return c, cfg, traffic


@contextlib.contextmanager
def planted(fault):
    """Break the timed path underneath the harness, in the program's own
    classes, for as long as the block runs.

    * ``state-unchanged``: every training step returns the state it got;
    * ``token-altered``: every 25th token the engine samples is changed;
    * ``cache-unchanged``: every decode step returns the slot cache it got.
    """
    import jax
    import jax.numpy as jnp
    from repro.runtime.engine import Engine
    from repro.training.steps import StepCache

    saved = []

    def patch(owner, name, value):
        saved.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    if fault == "state-unchanged":
        train = StepCache.train

        def broken_train(self, *a, **k):
            fn = train(self, *a, **k)

            def step(state, batch, rng, *extra):
                # the step donates its state: give it a copy, keep the original
                _, metrics = fn(jax.tree_util.tree_map(jnp.copy, state), batch, rng, *extra)
                return state, metrics

            return step

        patch(StepCache, "train", broken_train)
    elif fault == "token-altered":
        sample = Engine._sample
        hits = {"n": 0}

        def altered(self, req, row):
            tok = sample(self, req, row)
            hits["n"] += 1
            return (tok + 1) % self.cfg.vocab_size if hits["n"] % 25 == 0 else tok

        patch(Engine, "_sample", altered)
    elif fault == "cache-unchanged":
        decode_key_fn = Engine._decode_key_fn

        def frozen(self, approx, chip_aware=False):
            key, fn = decode_key_fn(self, approx, chip_aware)

            def step(params, cache, *rest):
                logits, _ = fn(params, jax.tree_util.tree_map(jnp.copy, cache), *rest)
                return logits, cache

            return key, step

        patch(Engine, "_decode_key_fn", frozen)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        for owner, name, value in reversed(saved):
            setattr(owner, name, value)


def execute(parts, seed, seconds, tmp_path, limits, fault=None, control=False):
    """Run a tiny cell in this process through the harness (no chip
    check, nothing printed), with ``fault`` planted; returns (result,
    context)."""
    import run as RUN

    c, cfg, traffic = parts
    ctx = RUN.Context(c, cfg, traffic, seed=seed, seconds=seconds, trace=False,
                      control=control, scratch=Path(tmp_path))
    with planted(fault):
        return RUN.execute(bench(), c, cfg, traffic, limits, ctx), ctx
