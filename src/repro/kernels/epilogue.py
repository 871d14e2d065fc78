"""Shared MODEL-mode epilogue math for fused approximate matmuls.

The unfused MODEL path applies three separate XLA ops after the backend
matmul: ``variation.apply_chip`` (per-column gain/offset or fault error,
scaled by the per-token row max), then an optional calibration
correction subtract (``y - predict_mean(stats, y)``).  The fused path
(:mod:`repro.kernels.ops`) applies the identical math to the output of
its one fused matmul kernel; this module holds the single definition
both sides share so bit-exactness is a property of the code, not a test
fixture.

Two invariants matter for exactness:

* ``eval_poly`` accumulates terms sequentially (term 0, then +term 1,
  ...) rather than via a stacked ``(V * coeffs).sum(-1)`` reduce, whose
  summation order XLA is free to rearrange between the fused and
  composed graphs.
* the per-token row scale is ``max(max|y|, eps)`` — a pure max chain,
  order-independent, so taking it in a separate pass over the output of
  an N-tiled kernel yields the same bits as over a whole row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_EPS = 1e-6


def eval_poly(coeffs, t):
    """Evaluate ``sum_i coeffs[..., i] * t**i`` with a fixed, sequential
    accumulation order (shared by the jnp path and the Pallas kernels)."""
    out = coeffs[..., 0] * jnp.ones_like(t)
    for i in range(1, coeffs.shape[-1]):
        out = out + coeffs[..., i] * t ** i
    return out


def row_abs_scale(y, eps: float = ROW_EPS):
    """Per-token activation scale: max(|y|) over the last axis, floored."""
    return jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(y), axis=-1, keepdims=True), eps)
    )


def apply_epilogue(
    y,
    colgain=None,
    coladd=None,
    mean_coeffs=None,
    mean_scale=None,
    eps: float = ROW_EPS,
):
    """Apply the chip + calibration epilogue to a matmul output tile.

    ``colgain``/``coladd`` replicate :func:`repro.hw.variation.apply_chip`
    for a fixed (site, backend) pair: gain families pass a per-column
    gain vector and a per-column offset (``y * colgain + coladd * scale``);
    fault families pass ``colgain=None`` and a per-column signed error
    (``y + coladd * scale``).  ``mean_coeffs``/``mean_scale`` replicate
    ``y - calibration.predict_mean(stats, y)``.

    All operands must already be cast to ``y.dtype`` (except the f32
    polynomial coefficients) exactly as the unfused path casts them.
    """
    if colgain is not None or coladd is not None:
        scale = row_abs_scale(y, eps).astype(y.dtype)
        if colgain is not None:
            y = y * colgain + coladd * scale
        else:
            y = y + coladd * scale
    if mean_coeffs is not None:
        t = y.astype(jnp.float32) / mean_scale
        y = y - eval_poly(mean_coeffs, t).astype(y.dtype)
    return y
