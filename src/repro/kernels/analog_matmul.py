"""Pallas TPU kernel: analog-array matmul with ADC partial-sum quantization.

The analog accelerator computes ``x @ w`` as a sequence of physical
array-sized dot products; each array's partial sum passes through a
low-bit ADC (clamp to the ADC range + round to 2^bits levels) before
digital accumulation (paper Sec. 2.2 / 3).

TPU mapping: this is a K-blocked matmul whose K-block equals the analog
array size.  Each (i, j, k) grid step computes one MXU-shaped (bm x bn)
tile of one array's partial sum in VMEM, applies the fake-ADC pointwise
quantizer on the VPU, and accumulates into a VMEM accumulator that stays
resident across the (sequential, innermost) k dimension.  With
``array_size = 128`` the contraction dim is exactly one MXU pass per
array.  The fused variant accumulates both unipolar weight planes in the
same grid, tile for tile, so its dots are the unfused kernel's dots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vpu_matmul import tile


def _adc_quantize(psum, adc_bits: int, adc_range: float):
    levels = (1 << adc_bits) - 1
    clamped = jnp.clip(psum, 0.0, adc_range)
    q = jnp.round(clamped / adc_range * levels) / levels * adc_range
    # The trailing minimum is a semantic no-op (q <= adc_range up to one
    # rounding) whose real job is keeping the final op a non-multiply:
    # XLA CPU contracts a multiply feeding an add/sub into an FMA, which
    # would let the same quantizer round differently depending on what
    # consumes it — breaking fused-vs-composed bit-exactness by an ulp.
    return jnp.minimum(q, adc_range)


def _kernel(x_ref, *refs, n_w: int, has_pre: bool, adc_bits: int,
            adc_range: float):
    w_refs = refs[:n_w]
    pre_ref = refs[n_w] if has_pre else None
    o_ref = refs[n_w + has_pre]
    acc_refs = refs[n_w + has_pre + 1:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        for acc in acc_refs:
            acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    for acc, w in zip(acc_refs, w_refs):
        # one analog array's raw partial sum for this (bm, bn) tile
        psum = jnp.dot(x, w[...], preferred_element_type=jnp.float32)
        acc[...] += _adc_quantize(psum, adc_bits, adc_range)

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        # the two planes accumulate independently and subtract once at
        # the end — Sum(adc_p) - Sum(adc_n), matching the composed
        # split_unipolar_contract order, not Sum(adc_p - adc_n)
        y = acc_refs[0][...]
        if n_w == 2:
            y = y - acc_refs[1][...]
        if has_pre:
            y = (y * pre_ref[...]).astype(o_ref.dtype)
        o_ref[...] = y


def _analog_call(x, ws, array_size, adc_bits, adc_range, prescale, out_dtype,
                 block_m, block_n, interpret):
    M, K = x.shape
    N = ws[0].shape[1]
    bm, Mp = tile(M, block_m, align=8)
    bn, Np = tile(N, block_n)
    Kp = -(-K // array_size) * array_size
    operands = [jnp.pad(x.astype(jnp.float32), ((0, Mp - M), (0, Kp - K)))]
    operands += [
        jnp.pad(w.astype(jnp.float32), ((0, Kp - K), (0, Np - N))) for w in ws
    ]
    in_specs = [pl.BlockSpec((bm, array_size), lambda i, j, k: (i, k))]
    in_specs += [pl.BlockSpec((array_size, bn), lambda i, j, k: (k, j))] * len(ws)
    has_pre = prescale is not None
    if has_pre:
        operands.append(jnp.asarray(prescale, jnp.float32).reshape(1, 1))
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)))

    out = pl.pallas_call(
        functools.partial(
            _kernel, n_w=len(ws), has_pre=has_pre,
            adc_bits=adc_bits, adc_range=adc_range,
        ),
        grid=(Mp // bm, Np // bn, Kp // array_size),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (Mp, Np), out_dtype if has_pre else jnp.float32
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32) for _ in ws],
        interpret=interpret,
        name="analog_matmul",
    )(*operands)
    return out[:M, :N]


def analog_matmul(
    x,
    w,
    array_size: int,
    adc_bits: int,
    adc_range: float,
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: bool = False,
):
    """x: [M, K] unipolar float32, w: [K, N] unipolar float32 -> [M, N]."""
    return _analog_call(
        x, [w], array_size, adc_bits, adc_range, None, jnp.float32,
        block_m, block_n, interpret,
    )


def analog_matmul_fused(
    x,
    w_pos,
    w_neg,
    array_size: int,
    adc_bits: int,
    adc_range: float,
    prescale,
    out_dtype,
    *,
    block_m: int = 128,
    block_n: int = 512,
    interpret: bool = False,
):
    """Fused dual-plane analog matmul: ``x @ w_pos - x @ w_neg`` with ADC
    partial-sum quantization per array, then the scalar rescale
    ``prescale`` (the composed path's ``sx * sw``) and the cast to
    ``out_dtype`` before the single writeback."""
    return _analog_call(
        x, [w_pos, w_neg], array_size, adc_bits, adc_range, prescale,
        out_dtype, block_m, block_n, interpret,
    )
