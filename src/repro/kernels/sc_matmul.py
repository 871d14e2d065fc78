"""Pallas TPU kernel: stochastic-computing matmul over packed bit-streams.

SC represents each unipolar value as a Bernoulli bit-stream; multiply is a
single AND gate, accumulate is an OR tree (paper Sec. 2.1, setup of [17]).
Emulating this is the expensive MODEL-mode forward (Tab. 1: 64x unrolled /
2x packed per op).

TPU mapping: the GPU/CPU version bit-twiddles LFSRs serially; on TPU we
instead (a) generate streams *outside* the kernel by threshold-comparing
values against shared per-port generator sequences, (b) pack them into
uint32 words, and (c) contract with the shared VPU scaffolding
(:func:`repro.kernels.vpu_matmul.contract`): AND the packed words,
OR-accumulate over K into a VMEM scratch accumulator, popcount once per
output tile on the last K step.  The stream-word axis ``W`` is the
leading plane axis of every block, not the lane axis, so a 32-bit stream
costs one word per value in VMEM rather than a 128-lane tile.

The packed-word values match ``ref.sc_matmul_packed_ref`` bit-for-bit,
so the kernel is validated bit-exactly against the oracle.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels.vpu_matmul import contract, popcount_value


def _planes(bits):
    """[..., W] packed words -> [W, ...] word planes."""
    return jnp.moveaxis(bits, -1, 0)


def _dual_value(acc_p, acc_n, n_bits: int):
    # each plane's popcount divides by n_bits independently before the
    # subtract, exactly like the two composed kernel calls
    return popcount_value(acc_p, n_bits) - popcount_value(acc_n, n_bits)


def sc_matmul_packed(xbits, wbits, n_bits: int, *, interpret: bool = False,
                     **blocks):
    """xbits: [M, K, W] uint32, wbits: [K, N, W] uint32 -> [M, N] float32
    stream value (popcount / n_bits) of the OR-accumulated AND products."""
    return contract(
        _planes(xbits), [_planes(wbits)],
        mul=jnp.bitwise_and, combine=jnp.bitwise_or,
        finish=functools.partial(popcount_value, n_bits=n_bits),
        name="sc_matmul", interpret=interpret, **blocks,
    )


def sc_matmul_packed_fused(
    xbits, wp_bits, wn_bits, n_bits: int, prescale, out_dtype, *,
    interpret: bool = False, **blocks,
):
    """Fused dual-plane SC contraction: the positive and negative stream
    planes OR-accumulate in parallel scratch, popcount once, subtract, and
    the scalar rescale ``prescale`` (the composed path's
    ``(sx * sw) / gain^2``) and the cast to ``out_dtype`` run before the
    single writeback."""
    return contract(
        _planes(xbits), [_planes(wp_bits), _planes(wn_bits)],
        mul=jnp.bitwise_and, combine=jnp.bitwise_or,
        finish=functools.partial(_dual_value, n_bits=n_bits),
        name="sc_matmul_fused", prescale=prescale, out_dtype=out_dtype,
        interpret=interpret, **blocks,
    )
