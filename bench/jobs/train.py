"""Training job: the program's ``Trainer`` driven from the seed.

Set-up builds one trainer, hands it a state made in one jitted call from
the seed, and lets its own loop run the first ``compare_steps`` steps
(the first compiles).  The window is the same loop carrying on: the
trainer's per-step callback marks each step's end, and raises
``WindowClosed`` at the first boundary past ``--seconds``.  The harness
wraps the trainer's compiled step to record the loss of the first steps,
the gradient norms held in the optimizer state after the first step and
the change of the master weights after the last compared step; once the
window has closed and the program's state is freed, the reference runs
the same steps and the two are compared.

The reference runs layer by layer: one compiled block forward and one
compiled block backward serve every layer, so its compile does not grow
with depth.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import checks
import ref_optim
import reference as R
import weights as W
from flops import dims
from program import approx_config, model_config, program_seed

# steps 0..2 run in set-up and are compared with the reference
COMPARE_STEPS = 3


class WindowClosed(Exception):
    """Raised from the trainer's per-step callback to end the window."""


class Batches:
    """Token rows from the seed: every row of every step differs."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed

    def batch_at(self, step: int):
        rng = np.random.default_rng([self.seed, step, 17])
        toks = rng.integers(0, self.vocab, (self.batch, self.seq + 1), dtype=np.int64)
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}


def train_config(t: Dict):
    from repro.configs.base import Phase, TrainConfig, TrainMode

    big = 10 ** 7
    return TrainConfig(
        learning_rate=float(t["learning_rate"]), min_lr_ratio=float(t["min_lr_ratio"]),
        warmup_steps=int(t["warmup_steps"]), total_steps=int(t["total_steps"]),
        weight_decay=float(t["weight_decay"]), beta1=float(t["beta1"]),
        beta2=float(t["beta2"]), eps=float(t["eps"]), grad_clip=float(t["grad_clip"]),
        remat=t["remat"], optim_compress=t["optim_compress"],
        checkpoint_every=big, phases=(Phase(TrainMode(t["mode"]), big),),
    )


def _grad_sq_from_v(v, n_cols_like):
    """Sum of squares of the clipped first gradient, per leaf, from the
    second moment after one step (v = (1 - b2) g**2, or its row means)."""
    if isinstance(v, dict):
        return jnp.sum(v["r"]) * n_cols_like.shape[-1]
    return jnp.sum(v)


class StepProbe:
    """Wraps the trainer's compiled step: a host span per step, and the
    readings of the first steps (taken before the state goes on)."""

    def __init__(self, fn, run):
        self.fn, self.run = fn, run

    def __call__(self, state, batch, rng, *extra):
        run = self.run
        i = run.calls
        run.calls += 1
        with jax.profiler.TraceAnnotation("bench.train_step"):
            new_state, metrics = self.fn(state, batch, rng, *extra)
        if i < COMPARE_STEPS:
            run.losses.append(float(metrics["loss"]))
            if i == 0:
                run.global_norm = float(metrics["grad_norm"])
                b2 = float(run.traffic["beta2"])
                sq = jax.tree_util.tree_map(
                    _grad_sq_from_v, new_state["opt"]["v"], new_state["params"],
                    is_leaf=lambda t: isinstance(t, dict) and set(t) == {"r", "c"},
                )
                run.grad_norms = [float(np.sqrt(float(s) / (1 - b2)))
                                  for s in jax.tree_util.tree_leaves(sq)]
            if i == COMPARE_STEPS - 1:
                run.change_norms = run.change_fn(new_state["opt"]["master"])
        return new_state, metrics


class TrainRun:
    def __init__(self, cfg: Dict, traffic: Dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.calls = 0
        self.losses: List[float] = []
        self.global_norm = float("nan")
        self.grad_norms: List[float] = []
        self.change_norms: List[float] = []
        self.marks: List = []

    def change_fn(self, master):
        """Per-leaf norm of the master weights' change since the served
        start.  The start is made in a call of its own, so that it is held
        in the served type: compiled together with the difference, the
        compiler may keep its float32 draw and skip the rounding."""
        init = W.params_from_seed(self.cfg, self.seed)
        norms = jax.jit(lambda master, init: [
            jnp.sqrt(jnp.sum((a - b.astype(jnp.float32)) ** 2))
            for a, b in zip(jax.tree_util.tree_leaves(master),
                            jax.tree_util.tree_leaves(init))])
        out = [float(x) for x in norms(master, init)]
        del init
        return out


def run(ctx) -> Dict:
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.runtime.trainer import Trainer

    cfg, t, seed = ctx.cfg, ctx.traffic, ctx.seed
    B, T = int(t["batch"]), int(t["seq"])
    model = build_model(model_config(cfg))
    approx = approx_config(t)
    tcfg = train_config(t)
    run_ = TrainRun(cfg, t, seed)

    t_init = time.perf_counter()
    params = W.params_from_seed(cfg, seed)

    @jax.jit
    def make_state(p):
        return {"params": p, "opt": adamw_init(p, tcfg.optim_compress),
                "calib": model.init_calibration(approx),
                "step": jnp.zeros((), jnp.int32)}

    state0 = make_state(params)
    jax.block_until_ready(state0)
    del params
    ctx.setup["init_s"] = time.perf_counter() - t_init

    trainer = Trainer(model, approx, tcfg, Batches(dims(cfg)["v"], B, T, seed),
                      str(ctx.scratch / "ckpt"), seed=program_seed(seed),
                      restart_budget=0)
    # the trainer's loop holds the only reference: it donates the state
    holder = [state0]
    del state0
    trainer.init_or_restore = holder.pop
    inner = trainer.steps.train
    trainer.steps.train = lambda *a, **k: StepProbe(inner(*a, **k), run_)
    window = {}

    def hook(step: int):
        now = time.perf_counter()
        run_.marks.append((step, now))
        if step == 1:
            ctx.setup["first_step_s"] = now - t_first
        if step == COMPARE_STEPS:
            ctx.start_window()
            window["t0"] = time.perf_counter()
            window["s0"] = step
        elif step > COMPARE_STEPS and now - window["t0"] >= ctx.seconds:
            window["t1"], window["s1"] = now, step
            raise WindowClosed()

    trainer.fault_hook = hook
    t_first = time.perf_counter()
    try:
        trainer.run(10 ** 7)
    except WindowClosed:
        pass
    ctx.stop_window()
    steps = window["s1"] - window["s0"]
    elapsed = window["t1"] - window["t0"]
    ctx.memory_peak()
    del trainer, inner
    gc.collect()

    tokens = steps * B * T
    step_marks = [m for m in run_.marks if m[0] >= window["s0"]]
    step_times = [b[1] - a[1] for a, b in zip(step_marks, step_marks[1:])]
    ctx.counters.update(
        steps=steps, tokens=tokens, window_s=elapsed, batch=B, seq=T,
        step_times=step_times,
    )
    ctx.e2e["train_tok_s"] = tokens / elapsed
    ctx.attempted, ctx.failed = steps + COMPARE_STEPS, 0

    t_ref = time.perf_counter()
    ref = reference_steps(cfg, t, seed, log=ctx.log)
    ctx.counters["reference_s"] = time.perf_counter() - t_ref
    ctx.log(f"losses program {run_.losses} reference {ref['losses']}")
    ctx.log(f"global norm program {run_.global_norm} reference {ref['global_norm']}")
    paths = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda k: W.make_params(cfg, k), W.seed_key(0)))[0]]
    for i, path in enumerate(paths):
        ctx.log(f"leaf {path} grad {run_.grad_norms[i]:.6g} ref {ref['grad_norms'][i]:.6g} "
                f"change {run_.change_norms[i]:.6g} ref {ref['change_norms'][i]:.6g}")
    if ctx.control:
        for name, kw in CONTROLS.items():
            other = reference_steps(cfg, t, seed, log=ctx.log, **kw)
            ctx.control_numbers[name] = checks.train_gaps(other, ref)
    return checks.train_gaps(
        {"losses": run_.losses, "global_norm": run_.global_norm,
         "grad_norms": run_.grad_norms, "change_norms": run_.change_norms}, ref)


# What a control run puts in the program's place: the reference with
# every product operand in float8 (the precision below the configured
# bfloat16), with the stochastic streams cut to half their length (the
# emulated datapath's precision below the configured one), and with half
# of each batch left out.
CONTROLS = {
    "fp8": {"cast": R.fp8_cast},
    "half_streams": {"stream_scale": 0.5},
    "half_batch": {"half_batch": True},
}

SITES = ("attn_q", "attn_k", "attn_v", "attn_o", "mlp_gate", "mlp_up", "mlp_down")


def reference_steps(cfg: Dict, t: Dict, seed: int, cast=R.no_cast,
                    half_batch=False, stream_scale=1.0, log=None) -> Dict:
    """The first steps of the cell in the plain reference.

    The served (bfloat16) weights and the gradients live on the device;
    the float32 master weights and moments wait on the host between
    steps, and each leaf is updated on the device in turn.  The forward
    and backward go one block at a time; each block's input is kept, and
    its backward recomputes the block."""
    B, T = int(t["batch"]), int(t["seq"])
    if stream_scale != 1.0:
        t = dict(t, sc_bits=max(1, int(int(t["sc_bits"]) * stream_scale)))
    n_layers = dims(cfg)["layers"]
    eps = float(cfg["rms_norm_eps"])
    data = Batches(dims(cfg)["v"], B, T, seed)
    product = R.product_fn(t, cast)
    served = W.params_from_seed(cfg, seed)
    leaves, tdef = jax.tree_util.tree_flatten(served)
    init = [np.asarray(p.astype(jnp.float32)) for p in leaves]
    master = [a.copy() for a in init]
    moment = [np.zeros_like(a) for a in init]
    second = [ref_optim.second_moment(a.shape) for a in init]
    f32 = lambda tree: jax.tree_util.tree_map(lambda q: q.astype(jnp.float32), tree)

    def block(x, p, step_key, layer):
        keys = {s: R.stream_key(step_key, layer, s) for s in SITES}
        with jax.default_matmul_precision("highest"):
            return R.layer_forward(x, f32(p), cfg, product, keys, cast)

    def head_loss(x, glob, labels, step_key):
        with jax.default_matmul_precision("highest"):
            h = R.rmsnorm(x, glob["final_norm"], eps)
            logits = product(h.reshape(B_ * T, -1), R.head_weight(glob, cfg),
                             R.stream_key(step_key, None, "lm_head"))
            return R.lm_loss(logits.reshape(B_, T, -1), labels)

    B_ = B // 2 if half_batch else B
    block_fwd = jax.jit(block)
    block_bwd = jax.jit(lambda x, p, k, l, gy: jax.vjp(
        lambda x_, p_: block(x_, p_, k, l), x, f32(p))[1](gy))
    head_vg = jax.jit(lambda x, glob, labels, k: jax.value_and_grad(
        head_loss, argnums=(0, 1))(x, f32(glob), labels, k))
    embed = jax.jit(lambda tok, ids: tok.astype(jnp.float32)[ids])
    embed_bwd = jax.jit(lambda g_tok, ids, gx: g_tok.at[ids].add(gx))
    stack = jax.jit(lambda grads: jax.tree_util.tree_map(lambda *g: jnp.stack(g), *grads))
    norm = jax.jit(lambda grads: jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                              for g in jax.tree_util.tree_leaves(grads))))
    clip = jax.jit(lambda grads, gnorm: jax.tree_util.tree_map(
        lambda g: g * ref_optim.clip_factor(gnorm, float(t["grad_clip"])), grads))
    take = jax.jit(lambda layers, l: jax.tree_util.tree_map(lambda a: a[l], layers))

    @jax.jit
    def step_leaf(master, m, v, g, h):
        new = ref_optim.update_leaf(master, m, v, g, **h)
        return new + (new[0].astype(jnp.bfloat16),)

    step_key = jax.random.PRNGKey(program_seed(seed) + 17)
    losses, grad_norms, global_norm = [], [], float("nan")
    for s in range(COMPARE_STEPS):
        t_step = time.perf_counter()
        b = data.batch_at(s)
        if half_batch:
            b = {k: v[: B // 2] for k, v in b.items()}
        key = jax.random.fold_in(step_key, s)
        glob = {k: v for k, v in served.items() if k != "layers"}
        xs = [embed(served["embed"]["tok"], b["tokens"])]
        for l in range(n_layers):
            xs.append(block_fwd(xs[-1], take(served["layers"], l), key, l))
        loss, (gx, g_glob) = head_vg(xs.pop(), glob, b["labels"], key)
        g_layers = [None] * n_layers
        for l in reversed(range(n_layers)):
            gx, g_layers[l] = block_bwd(xs.pop(), take(served["layers"], l), key, l, gx)
        g_glob["embed"]["tok"] = embed_bwd(g_glob["embed"]["tok"], b["tokens"], gx)
        grads = dict(g_glob, layers=stack(g_layers))
        del g_layers, g_glob, gx
        gnorm = norm(grads)
        grads = clip(grads, gnorm)
        losses.append(float(loss))
        g_leaves = jax.tree_util.tree_leaves(grads)
        del grads
        if s == 0:
            global_norm = float(gnorm)
            grad_norms = [float(jnp.sqrt(jnp.sum(g * g))) for g in g_leaves]
        h = ref_optim.hyper(s + 1, t)
        new_served = []
        for i in range(len(g_leaves)):
            mw, m, v, sv = step_leaf(master[i], moment[i], second[i], g_leaves[i], h)
            g_leaves[i] = None
            master[i], moment[i], second[i] = np.asarray(mw), np.asarray(m), v
            new_served.append(sv)
        served = tdef.unflatten(new_served)
        if log is not None:
            log(f"reference step {s} {time.perf_counter() - t_step:.2f} s")
    change = [float(np.sqrt(np.sum((a - b).astype(np.float64) ** 2)))
              for a, b in zip(master, init)]
    return {"losses": losses, "global_norm": global_norm, "grad_norms": grad_norms,
            "change_norms": change}
