"""Shared Pallas scaffolding for contractions that cannot use the MXU.

Backends whose error enters *per multiplication* (truncated approximate
multiplier, Mitchell log multiplier) pass every product through a
non-linear scalar op on the VPU.  They share one TPU mapping and differ
only in the per-product op (``mul``):

* grid ``(M blocks, N blocks, K blocks)``, K innermost and sequential;
* the activation is laid out ``[K, M]`` and the weight ``[K, N]``, so one
  K step reads row ``i`` of both with ``pl.ds`` on the second-minor axis
  — never a dynamic index on the lane axis, which the TPU compiler
  refuses;
* a rank-1 update per K step into a ``(bm, bn)`` float32 VMEM accumulator.

N is tiled, so an 11008-wide projection or a 151936-wide LM head never
needs more than one ``(bm, bn)`` tile in VMEM.
"""
from __future__ import annotations

import functools
from typing import Callable

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


def _kernel(x_ref, w_ref, *refs, mul, has_pre: bool):
    pre_ref = refs[0] if has_pre else None
    o_ref, acc_ref = refs[has_pre:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    block_k, bm = x_ref.shape

    def body(i, acc):
        # column i of the activation tile, read as a sublane row and
        # turned into a [bm, 1] column for the rank-1 update
        xc = x_ref[pl.ds(i, 1), :].reshape(bm, 1)
        return acc + mul(xc, w_ref[pl.ds(i, 1), :])

    acc_ref[...] = jax.lax.fori_loop(0, block_k, body, acc_ref[...])

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        y = acc_ref[...]
        if has_pre:
            y = (y * pre_ref[...]).astype(o_ref.dtype)
        o_ref[...] = y


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

    ``mul`` must map zero operands to zero (K, M and N padding is
    zero-filled).  With ``prescale`` ([M, 1] per-token rescale, or a
    scalar) the f32 accumulator is multiplied by it and cast to
    ``out_dtype`` before the writeback — the composed path's
    ``(acc * prescale).astype(dtype)``, fused.  ``name`` is the kernel's
    name in a profile (the calling backend's).
    """
    M, K = x.shape
    N = w.shape[-1]
    bm, Mp = tile(M, block_m, align=8)
    bn, Np = tile(N, block_n)
    bk, Kp = tile(K, block_k)
    xt = jnp.pad(x.astype(jnp.float32).T, ((0, Kp - K), (0, Mp - M)))
    w = jnp.pad(w.astype(jnp.float32), ((0, Kp - K), (0, Np - N)))

    operands = [xt, w]
    in_specs = [
        pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    has_pre = prescale is not None
    if has_pre:
        pre = jnp.broadcast_to(
            jnp.asarray(prescale, jnp.float32).reshape(-1, 1), (M, 1)
        )
        operands.append(jnp.pad(pre, ((0, Mp - M), (0, 0))))
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, mul=mul, has_pre=has_pre),
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (Mp, Np), out_dtype if has_pre else jnp.float32
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name=name,
    )(*operands)
    return out[:M, :N]
