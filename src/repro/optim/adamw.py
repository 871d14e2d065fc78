"""AdamW with f32 master weights, global-norm clipping, cosine schedule.

Written from scratch (no optax in this environment).  Optimizer state is a
plain pytree dict so it shards/checkpoints like everything else:
``{"m", "v", "master", "count"}``.  ``master`` holds f32 master copies
when params train in bf16 (mixed precision); m/v are always f32 under the
default ``TrainConfig.optim_compress="none"``.

Compressed optimizer state (the training-memory half of the approximate-
training story): ``optim_compress="bf16"`` stores the first moment in
bf16 with *stochastic rounding* — the EMA still computes in f32 each
step, and the random rounding direction makes the quantization error
zero-mean so small gradient contributions are not systematically lost
below the bf16 mantissa.  ``optim_compress="sm3"`` additionally replaces
the full second moment of every matrix-shaped leaf with SM3/Adafactor-
style factored statistics: a row vector ``r`` (EMA of the per-row mean of
``g**2``) and a column vector ``c``, reconstructing
``v_hat = r[..., :, None] * c[..., None, :] / mean(r)`` — exact when
``g**2`` is rank-1, O(n+m) memory instead of O(n*m).  The rounding rng is
derived from the step count, so optimizer updates are bitwise
reproducible across a checkpoint restore (tested by
tests/test_approx_bwd.py round-trip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.utils.tree import tree_global_norm


def lr_at(step, cfg: TrainConfig):
    """Linear warmup + cosine decay to min_lr_ratio."""
    step = jnp.asarray(step, jnp.float32)
    warm = cfg.learning_rate * step / jnp.maximum(cfg.warmup_steps, 1)
    prog = jnp.clip(
        (step - cfg.warmup_steps)
        / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0,
        1.0,
    )
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.learning_rate * cos)


def _is_factored(t) -> bool:
    """Leaf predicate for the factored second-moment {"r", "c"} pairs."""
    return isinstance(t, dict) and set(t) == {"r", "c"}


def _factorable(x) -> bool:
    """SM3 factoring applies to matrix-shaped leaves only; vectors and
    scalars keep the full (already tiny) second moment."""
    return x.ndim >= 2


def adamw_init(params, compress: str = "none"):
    """Optimizer state for ``params``.  ``compress`` mirrors
    ``TrainConfig.optim_compress``: "none" (all f32), "bf16" (bf16 first
    moment), "sm3" (bf16 first moment + factored second moment)."""
    if compress not in ("none", "bf16", "sm3"):
        raise ValueError(f"unknown optim_compress {compress!r}")
    m_dtype = jnp.float32 if compress == "none" else jnp.bfloat16

    def init_m(x):
        return jnp.zeros(x.shape, m_dtype)

    def init_v(x):
        if compress == "sm3" and _factorable(x):
            return {
                "r": jnp.zeros(x.shape[:-1], jnp.float32),
                "c": jnp.zeros(x.shape[:-2] + x.shape[-1:], jnp.float32),
            }
        return jnp.zeros(x.shape, jnp.float32)

    # a buffer of its own even where params are already f32: a train step
    # that donates its state cannot donate one buffer twice
    master = jax.tree_util.tree_map(
        lambda x: jnp.array(x, jnp.float32, copy=True), params
    )
    return {
        "m": jax.tree_util.tree_map(init_m, params),
        "v": jax.tree_util.tree_map(init_v, params),
        "master": master,
        "count": jnp.zeros((), jnp.int32),
    }


def _stochastic_round_bf16(x, key):
    """f32 -> bf16 with stochastic rounding (unbiased).

    bf16 is f32 with the low 16 mantissa bits dropped; adding uniform
    random low bits before truncation rounds up with probability equal to
    the dropped fraction — E[round(x)] == x, so momentum EMAs keep
    sub-mantissa gradient mass in expectation instead of flushing it.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    noise = jax.random.randint(
        key, x.shape, 0, 1 << 16, dtype=jnp.uint32
    )
    return jax.lax.bitcast_convert_type(
        (bits + noise) & jnp.uint32(0xFFFF0000), jnp.float32
    ).astype(jnp.bfloat16)


def _factored_vhat(f, eps: float):
    """Reconstruct the full second-moment estimate from {"r", "c"}."""
    r, c = f["r"], f["c"]
    # mean(r) == mean(c) == mean(g^2 EMA); dividing one factor's product
    # by it makes the outer product exact for rank-1 g^2 (Adafactor).
    denom = jnp.maximum(jnp.mean(r, axis=-1, keepdims=True), eps)
    return (r / denom)[..., :, None] * c[..., None, :]


def _decay_mask(path) -> bool:
    """Weight decay only on matrices (norms/biases/scalars excluded)."""
    return True


def state_bytes(opt) -> int:
    """Total bytes of the m/v slots (the compressible part of the state;
    master weights are a mixed-precision concern, not a compression one).
    What ``optim_compress`` is buying — asserted by tests and reported by
    bench_train_speed."""
    total = 0
    for slot in ("m", "v"):
        for leaf in jax.tree_util.tree_leaves(opt[slot]):
            total += leaf.size * leaf.dtype.itemsize
    return total


def adamw_update(grads, opt, params, cfg: TrainConfig):
    """Returns (new_params, new_opt, metrics)."""
    compress = getattr(cfg, "optim_compress", "none")
    count = opt["count"] + 1
    lr = lr_at(count, cfg)

    gnorm = tree_global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32) * scale, grads)

    b1, b2 = cfg.beta1, cfg.beta2
    # First moment: EMA computed in f32 (bf16 state upcast on read).
    m_f32 = jax.tree_util.tree_map(
        lambda m_, g: b1 * m_.astype(jnp.float32) + (1 - b1) * g,
        opt["m"], grads,
    )
    if compress == "none":
        m_store = m_f32
    else:
        # Stochastic rounding keyed on the step count: deterministic given
        # the count, so a checkpoint-restored run replays bitwise.
        base_key = jax.random.fold_in(
            jax.random.PRNGKey(0x5F3759DF), count
        )
        leaves, treedef = jax.tree_util.tree_flatten(m_f32)
        keys = jax.random.split(base_key, len(leaves))
        m_store = jax.tree_util.tree_unflatten(
            treedef,
            [_stochastic_round_bf16(l, k) for l, k in zip(leaves, keys)],
        )

    def upd_v(v_, g):
        if _is_factored(v_):
            g2 = jnp.square(g)
            return {
                "r": b2 * v_["r"] + (1 - b2) * jnp.mean(g2, axis=-1),
                "c": b2 * v_["c"] + (1 - b2) * jnp.mean(g2, axis=-2),
            }
        return b2 * v_ + (1 - b2) * jnp.square(g)

    v = jax.tree_util.tree_map(upd_v, opt["v"], grads, is_leaf=_is_factored)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def upd(master, m_, v_):
        vhat = _factored_vhat(v_, cfg.eps) if _is_factored(v_) else v_
        step = m_.astype(jnp.float32) / c1 / (jnp.sqrt(vhat / c2) + cfg.eps)
        wd = cfg.weight_decay * master if master.ndim >= 2 else 0.0
        return master - lr * (step + wd)

    master = jax.tree_util.tree_map(
        upd, opt["master"], m_f32, v,
        is_leaf=lambda t: _is_factored(t) or not isinstance(t, dict),
    )
    new_params = jax.tree_util.tree_map(
        lambda mw, p: mw.astype(p.dtype), master, params
    )
    new_opt = {"m": m_store, "v": v, "master": master, "count": count}
    return new_params, new_opt, {"grad_norm": gnorm, "lr": lr}
