"""Pallas TPU kernel: matmul through the Mitchell log-domain multiplier.

Like the truncated approximate multiplier, the error enters *per
multiplication* (accumulation is exact), so the contraction runs on the
VPU through the shared ``vpu_matmul`` scaffolding.  The per-product op IS
the oracle ``ref.mitchell_mul`` (pure jnp, usable inside the kernel), so
the kernel-vs-oracle validation in tests can never silently diverge on
the math — only on the blocking/accumulation, which is what it's for.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.vpu_matmul import elementwise_matmul


def log_matmul(
    x,
    w,
    *,
    prescale=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
    **blocks,
):
    """x: [M, K] integer-valued floats, w: [K, N] likewise -> [M, N] f32.

    With ``prescale`` ([M, 1]) the accumulator is rescaled and cast to
    ``out_dtype`` in the kernel (the fused MODEL-mode entry point)."""
    return elementwise_matmul(
        x, w, ref.mitchell_mul, name="log_matmul",
        prescale=prescale, out_dtype=out_dtype, interpret=interpret, **blocks,
    )
