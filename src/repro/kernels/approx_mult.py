"""Pallas TPU kernel: matmul through a behavioural approximate multiplier.

The approximate multiplier introduces error per-multiplication (exact
accumulation), so the contraction cannot use the MXU — every product must
pass through the non-linear truncation individually.  This is exactly the
paper's Tab. 1 cost story (86 ops per multiply on CPU; a VPU elementwise
loop here).  The blocking/accumulation scaffolding is shared with the
other multiplier-error kernels in ``vpu_matmul``; the truncated-product
model ``sign(ab) * floor(|ab| / 2^d) * 2^d`` is the per-product op.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.vpu_matmul import elementwise_matmul


def _approx_mul(a, b, drop_scale: float):
    prod = a * b
    mag = jnp.floor(jnp.abs(prod) / drop_scale) * drop_scale
    return jnp.sign(prod) * mag


def approx_mult_matmul(
    x,
    w,
    mult_bits: int,
    perforate: int,
    *,
    prescale=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
    **blocks,
):
    """x: [M, K] integer-valued floats in [-(2^b-1), 2^b-1], w: [K, N].

    With ``prescale`` ([M, 1]) the accumulator is rescaled and cast to
    ``out_dtype`` in the kernel (the fused MODEL-mode entry point)."""
    del mult_bits
    drop_scale = float(1 << (2 * perforate))
    return elementwise_matmul(
        x, w, lambda a, b: _approx_mul(a, b, drop_scale),
        name="approx_mult_matmul", prescale=prescale, out_dtype=out_dtype, interpret=interpret, **blocks,
    )
