"""Plain reference of the optimizer the training cells state: AdamW with
float32 master weights, global-norm clipping, linear warm-up then cosine
decay, and (``sm3``) a factored second moment for every leaf of two or
more axes: row and column means of g**2, rebuilt as
``r[:, None] * c[None, :] / mean(r)``.

It updates one leaf at a time, so that a caller can keep the master
weights and moments off the device between steps.

One departure: the program stores the first moment in bfloat16 with
stochastic rounding; the reference keeps it in float32.  The rounding is
unbiased, so the two differ by rounding alone.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def lr_at(count: int, t: Dict) -> float:
    lr, warm, total = float(t["learning_rate"]), int(t["warmup_steps"]), int(t["total_steps"])
    if count < warm:
        return lr * count / max(warm, 1)
    prog = min(max((count - warm) / max(total - warm, 1), 0.0), 1.0)
    floor = float(t["min_lr_ratio"])
    return lr * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * prog)))


def second_moment(shape):
    if len(shape) >= 2:
        return {"r": jnp.zeros(shape[:-1], F32), "c": jnp.zeros(shape[:-2] + shape[-1:], F32)}
    return jnp.zeros(shape, F32)


def clip_factor(global_norm, max_norm: float):
    """The factor that clips a gradient of this global norm."""
    return jnp.minimum(1.0, max_norm / (global_norm + 1e-9))


def update_leaf(master, m, v, g, lr, c1, c2, b1, b2, eps, wd):
    """One leaf's step with an already clipped gradient ``g``."""
    m = b1 * m + (1 - b1) * g
    if isinstance(v, dict):
        g2 = g * g
        v = {"r": b2 * v["r"] + (1 - b2) * jnp.mean(g2, axis=-1),
             "c": b2 * v["c"] + (1 - b2) * jnp.mean(g2, axis=-2)}
        den = jnp.maximum(jnp.mean(v["r"], axis=-1, keepdims=True), eps)
        vhat = (v["r"] / den)[..., :, None] * v["c"][..., None, :]
    else:
        v = b2 * v + (1 - b2) * g * g
        vhat = v
    step = m / c1 / (jnp.sqrt(vhat / c2) + eps)
    decay = wd * master if master.ndim >= 2 else 0.0
    return master - lr * (step + decay), m, v


def hyper(count: int, t: Dict) -> Dict:
    b1, b2 = float(t["beta1"]), float(t["beta2"])
    return {"lr": lr_at(count, t), "c1": 1 - b1 ** count, "c2": 1 - b2 ** count,
            "b1": b1, "b2": b2, "eps": float(t["eps"]), "wd": float(t["weight_decay"])}
