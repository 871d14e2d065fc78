"""Shared Pallas scaffolding for contractions that cannot use the MXU.

Backends whose error enters *per multiplication* (truncated approximate
multiplier, Mitchell log multiplier) and the stochastic-computing AND/OR
stream contraction all pass every product through a non-linear scalar op
on the VPU.  They share one TPU mapping, and differ only in the per-product
op (``mul``), the accumulation (``combine``: add, or bitwise OR) and how
the accumulator becomes the output (``finish``):

* grid ``(M blocks, N blocks, K blocks)``, K innermost and sequential;
* the activation is laid out ``[P, K, M]`` and the weight ``[P, K, N]``
  (``P`` word planes, 1 for the float backends), so one K step reads row
  ``i`` of both with ``pl.ds`` on the second-minor axis — never a dynamic
  index on the lane axis, which the TPU compiler refuses;
* a rank-1 update per K step into a ``(P, bm, bn)`` VMEM accumulator,
  one per weight plane (the fused dual-plane kernels pass two).

N is tiled, so an 11008-wide projection or a 151936-wide LM head never
needs more than one ``(bm, bn)`` tile per plane in VMEM.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tile(n: int, block: int, align: int = 1):
    """``(block size, padded extent)`` for an axis of length ``n``: one
    block spanning the whole (``align``-padded) axis when it fits, else
    ``block``-sized tiles over ``n`` padded to a multiple of ``block``."""
    if n <= block:
        n = _round_up(n, align)
        return n, n
    return block, _round_up(n, block)


def _kernel(x_ref, *refs, mul, combine, finish, n_w: int, has_pre: bool):
    w_refs = refs[:n_w]
    pre_ref = refs[n_w] if has_pre else None
    o_ref = refs[n_w + has_pre]
    acc_refs = refs[n_w + has_pre + 1:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        for acc in acc_refs:
            acc[...] = jnp.zeros_like(acc)

    n_planes, block_k, bm = x_ref.shape
    for p in range(n_planes):

        def body(i, accs):
            # column i of the activation tile, read as a sublane row and
            # turned into a [bm, 1] column for the rank-1 update
            xc = x_ref[p, pl.ds(i, 1), :].reshape(bm, 1)
            return tuple(
                combine(acc, mul(xc, w[p, pl.ds(i, 1), :]))
                for acc, w in zip(accs, w_refs)
            )

        accs = jax.lax.fori_loop(
            0, block_k, body, tuple(acc[p] for acc in acc_refs)
        )
        for acc, v in zip(acc_refs, accs):
            acc[p] = v

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        y = finish(*(acc[...] for acc in acc_refs))
        if has_pre:
            y = (y * pre_ref[...]).astype(o_ref.dtype)
        o_ref[...] = y


def contract(
    x,
    ws: Sequence[jax.Array],
    *,
    mul: Callable,
    combine: Callable,
    finish: Callable,
    name: str,
    prescale=None,
    out_dtype=jnp.float32,
    block_m: int = 128,
    block_n: int = 512,
    block_k: int = 128,
    interpret: bool = False,
):
    """Elementwise-product contraction over word planes.

    x: [P, M, K]; each of ``ws``: [P, K, N] (same dtype as ``x``).
    Accumulates ``acc[p] = combine(acc[p], mul(x[p, :, k, None], w[p, k]))``
    over k in order, one accumulator per weight plane, then writes
    ``finish(*accs)`` ([M, N] f32) — times ``prescale`` ([M, 1] or a
    scalar) and cast to ``out_dtype`` when a prescale is given.

    ``mul`` must map zero operands to the accumulator's identity (K, M and
    N padding is zero-filled).  ``name`` is the kernel's name in a
    profile (the calling backend's).
    """
    P, M, K = x.shape
    N = ws[0].shape[-1]
    bm, Mp = tile(M, block_m, align=8)
    bn, Np = tile(N, block_n)
    bk, Kp = tile(K, block_k)
    xt = jnp.pad(jnp.swapaxes(x, 1, 2), ((0, 0), (0, Kp - K), (0, Mp - M)))
    ws = [jnp.pad(w, ((0, 0), (0, Kp - K), (0, Np - N))) for w in ws]

    operands = [xt, *ws]
    in_specs = [pl.BlockSpec((P, bk, bm), lambda i, j, k: (0, k, i))]
    in_specs += [pl.BlockSpec((P, bk, bn), lambda i, j, k: (0, k, j))] * len(ws)
    has_pre = prescale is not None
    if has_pre:
        pre = jnp.broadcast_to(
            jnp.asarray(prescale, jnp.float32).reshape(-1, 1), (M, 1)
        )
        operands.append(jnp.pad(pre, ((0, Mp - M), (0, 0))))
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)))

    out = pl.pallas_call(
        functools.partial(
            _kernel, mul=mul, combine=combine, finish=finish,
            n_w=len(ws), has_pre=has_pre,
        ),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (Mp, Np), out_dtype if has_pre else jnp.float32
        ),
        scratch_shapes=[pltpu.VMEM((P, bm, bn), x.dtype) for _ in ws],
        interpret=interpret,
        name=name,
    )(*operands)
    return out[:M, :N]


def _sum_plane(acc):
    return acc[0]


def elementwise_matmul(
    x,
    w,
    mul: Callable,
    *,
    name: str,
    prescale=None,
    out_dtype=jnp.float32,
    block_m: int = 128,
    block_n: int = 512,
    block_k: int = 128,
    interpret: bool = False,
):
    """[M,K] @ [K,N] -> [M,N] f32 with every product through ``mul(a, b)``
    and exact f32 accumulation in K order.

    With ``prescale`` ([M, 1] per-token rescale) the f32 accumulator is
    multiplied by it and cast to ``out_dtype`` before the writeback — the
    composed path's ``(acc * prescale).astype(dtype)``, fused.
    """
    return contract(
        x.astype(jnp.float32)[None], [w.astype(jnp.float32)[None]],
        mul=mul, combine=jnp.add, finish=_sum_plane, name=name,
        prescale=prescale, out_dtype=out_dtype,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )


def popcount_value(acc, n_bits: int):
    """Stream value of an OR-accumulated word tile [W, bm, bn]: the
    popcount summed over words, over the stream length."""
    counts = jax.lax.population_count(acc).astype(jnp.int32)
    return counts.astype(jnp.float32).sum(0) / n_bits
