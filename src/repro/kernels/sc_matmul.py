"""Pallas TPU kernel: stochastic-computing matmul over packed bit-streams.

SC represents each unipolar value as a Bernoulli bit-stream; multiply is a
single AND gate, accumulate is an OR tree (paper Sec. 2.1, setup of [17]).
Emulating this is the expensive MODEL-mode forward (Tab. 1: 64x unrolled /
2x packed per op).

TPU mapping: the GPU/CPU version bit-twiddles LFSRs serially; on TPU we
instead (a) generate streams *outside* the kernel by threshold-comparing
values against shared per-port generator sequences, (b) pack them into
uint32 words (``W`` words per stream), and (c) contract the packed words
on the MXU (kernels ``sc_matmul_mxu`` and ``sc_matmul_fused_mxu``).

OR over k of (x_k AND w_k) is exact integer arithmetic: bit b of the
result is 1 iff ``sum_k x_k[b] * w_k[b] > 0``, and that sum is a 0/1
matrix product.  Each grid step unpacks bit b of the x tile ``[bm, bk]``
and the w tile ``[bk, bn]`` of one word plane into 0/1 operands, counts
on the MXU, and OR-folds ``(count > 0) << b`` into a packed uint32
accumulator: 32 products per word, folded once per K block.  The
operands are int8 and the counts accumulate in int32, so they are exact
(a block holds at most ``bk`` ones).  A count is never held in bfloat16,
which is exact only up to 256: an MXU result in bfloat16 could round a
count, and float32 counts of bfloat16 operands, also exact, ran about
1.7x slower on a v5e.  Word planes are a grid axis outside K; each is
popcounted into an int32 count when its K loop ends, so VMEM does not
grow with the stream length.

The MXU pass pays off at every row count: a rank-1 AND/OR loop on the
VPU over the same packed words was 1.9-5.9x slower per call on a v5e at
4 to 128 rows (decode batches) and 12x slower at 512 (a training step),
so there is no VPU route for few rows.  Rows are padded to a multiple of
32 (of ``block_m`` above it).

Zero padding of K, M and N is the identity (0 AND anything = 0).

The packed-word values match ``ref.sc_matmul_packed_ref`` bit-for-bit,
so the kernel is validated bit-exactly against the oracle.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vpu_matmul import tile

WORD_BITS = 32
# bits of a word unrolled per iteration of the kernel's bit loop
_BITS_PER_ITER = 4


def _planes(bits):
    """[..., W] packed words -> [W, ...] word planes."""
    return jnp.moveaxis(bits, -1, 0)


def _bit(words, b):
    """Bit ``b`` of each uint32 word as a 0/1 int8 MXU operand."""
    return ((words >> b) & 1).astype(jnp.int32).astype(jnp.int8)


def _mxu_kernel(x_ref, *refs, n_w: int, has_pre: bool, n_bits: int):
    w_refs = refs[:n_w]
    pre_ref = refs[n_w] if has_pre else None
    o_ref = refs[n_w + has_pre]
    acc_refs = refs[n_w + has_pre + 1:][:n_w]
    cnt_refs = refs[n_w + has_pre + 1:][n_w:]
    p, k = pl.program_id(2), pl.program_id(3)
    last_k = k == pl.num_programs(3) - 1

    @pl.when(k == 0)
    def _init():
        for acc in acc_refs:
            acc[...] = jnp.zeros_like(acc)

    def fold_bits(g, carry):
        for j in range(_BITS_PER_ITER):
            b = (g * _BITS_PER_ITER + j).astype(jnp.uint32)
            xb = _bit(x_ref[...], b)  # one unpack serves every weight plane
            for acc, w in zip(acc_refs, w_refs):
                hits = jnp.dot(xb, _bit(w[...], b), preferred_element_type=jnp.int32)
                acc[...] |= jnp.where(hits > 0, jnp.uint32(1) << b, jnp.uint32(0))
        return carry

    jax.lax.fori_loop(0, WORD_BITS // _BITS_PER_ITER, fold_bits, 0)

    @pl.when(last_k)
    def _popcount():
        for acc, cnt in zip(acc_refs, cnt_refs):
            ones = jax.lax.population_count(acc[...]).astype(jnp.int32)
            cnt[...] = ones + jnp.where(p == 0, 0, cnt[...])

    @pl.when(last_k & (p == pl.num_programs(2) - 1))
    def _finish():
        # each plane's count divides by n_bits before the subtract,
        # exactly like the two composed kernel calls
        y = cnt_refs[0][...].astype(jnp.float32) / n_bits
        if n_w == 2:
            y = y - cnt_refs[1][...].astype(jnp.float32) / n_bits
        if has_pre:
            y = (y * pre_ref[...]).astype(o_ref.dtype)
        o_ref[...] = y


def mxu_contract(
    x,
    ws: Sequence[jax.Array],
    n_bits: int,
    *,
    name: str,
    prescale=None,
    out_dtype=jnp.float32,
    block_m: int = 512,
    block_n: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """OR-of-AND contraction of packed words through 0/1 MXU products.

    x: [W, M, K] uint32; ``ws``: one or two (positive, negative) weight
    planes [W, K, N] uint32.  Writes the stream value ([M, N] f32: the
    popcount over ``n_bits``, the second plane's subtracted), times
    ``prescale`` ([M, 1] or a scalar) and cast to ``out_dtype`` when a
    prescale is given.  Word planes are a grid axis outside K, each
    popcounted into an int32 count when its K loop ends, so VMEM does not
    grow with the stream length.
    """
    W, M, K = x.shape
    N = ws[0].shape[-1]
    bm, Mp = tile(M, block_m, align=32)
    bn, Np = tile(N, block_n, align=128)
    bk, Kp = tile(K, block_k, align=128)
    x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, Kp - K)))
    ws = [jnp.pad(w, ((0, 0), (0, Kp - K), (0, Np - N))) for w in ws]

    operands = [x, *ws]
    in_specs = [pl.BlockSpec((None, bm, bk), lambda i, j, p, k: (p, i, k))]
    in_specs += [
        pl.BlockSpec((None, bk, bn), lambda i, j, p, k: (p, k, j))
    ] * len(ws)
    has_pre = prescale is not None
    if has_pre:
        pre = jnp.broadcast_to(
            jnp.asarray(prescale, jnp.float32).reshape(-1, 1), (M, 1)
        )
        operands.append(jnp.pad(pre, ((0, Mp - M), (0, 0))))
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, p, k: (i, 0)))

    out = pl.pallas_call(
        functools.partial(
            _mxu_kernel, n_w=len(ws), has_pre=has_pre, n_bits=n_bits
        ),
        grid=(Mp // bm, Np // bn, W, Kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, p, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (Mp, Np), out_dtype if has_pre else jnp.float32
        ),
        scratch_shapes=(
            [pltpu.VMEM((bm, bn), jnp.uint32) for _ in ws]
            + [pltpu.VMEM((bm, bn), jnp.int32) for _ in ws]
        ),
        interpret=interpret,
        name=name,
    )(*operands)
    return out[:M, :N]


def sc_matmul_packed(xbits, wbits, n_bits: int, *, interpret: bool = False,
                     **blocks):
    """xbits: [M, K, W] uint32, wbits: [K, N, W] uint32 -> [M, N] float32
    stream value (popcount / n_bits) of the OR-accumulated AND products."""
    return mxu_contract(
        _planes(xbits), [_planes(wbits)], n_bits, name="sc_matmul_mxu",
        interpret=interpret, **blocks,
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
    return mxu_contract(
        _planes(xbits), [_planes(wp_bits), _planes(wn_bits)], n_bits,
        name="sc_matmul_fused_mxu", prescale=prescale, out_dtype=out_dtype,
        interpret=interpret, **blocks,
    )
