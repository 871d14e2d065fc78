"""Pallas TPU kernel: flash-style single-token decode attention.

The jnp decode path materializes the full ``[B, KV, G, S]`` f32 logits
tensor over the entire padded cache every step — an HBM round trip that
dominates decode at serving cache lengths.  This kernel streams the KV
cache in blocks with an online softmax (running max ``m``, running
normalizer ``l``, rescaled accumulator), so only one ``[G, block_s]``
logit slab is ever resident.

Continuous batching makes the cache ragged: every slot sits at its own
``pos``.  Blocks strictly past a row's position are skipped outright
(bucketing — the @pl.when guard below), and the straddling block masks
per-element with the same NEG_INF the jnp path uses.

TPU layout: the cache is viewed as ``[B, S, KV * dh]`` (a free reshape),
so every K/V block is a ``(bs, dh)`` tile of one KV head; per-row
positions arrive by scalar prefetch in SMEM, where both the kernel and
the block index maps can read them.

Equivalence to ``models.layers.decode_attention`` is allclose, not
bitwise: online softmax reassociates the normalizer sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, l_ref, m_ref, *, block_s: int
):
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    pos = pos_ref[pl.program_id(0)]
    start = s * block_s

    # bucketed skip: blocks wholly past this row's position are neither
    # computed here nor (see kv_block in flash_decode) copied in
    @pl.when(start <= pos)
    def _compute():
        dh = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32)  # [G, dh]
        k = k_ref[0].astype(jnp.float32)  # [bs, dh]
        v = v_ref[0].astype(jnp.float32)  # [bs, dh]
        logits = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * (
            dh ** -0.5
        )  # [G, bs]
        idx = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        logits = jnp.where(idx <= pos, logits, NEG_INF)
        m_prev = m_ref[...]  # [G, 1]
        m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(s == ns - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...] / l_ref[...]


def _block_s(S: int, cap: int = 128) -> int:
    for b in range(min(cap, S), 0, -1):
        if S % b == 0:
            return b
    return 1


def flash_decode(q, cache_k, cache_v, pos_vec, *, interpret: bool = False):
    """q: [B, KV, G, dh]; cache_k/v: [B, S, KV, dh]; pos_vec: [B] int32.

    Returns [B, KV, G, dh] float32 attention output (same contraction as
    the einsum pair in ``decode_attention``, minus the full-S logits
    materialization).
    """
    B, KV, G, dh = q.shape
    S = cache_k.shape[1]
    bs = _block_s(S)

    def kv_block(b, kv, s, pos_ref):
        # a block wholly past the row's position maps to the row's last
        # live block: the pipeline skips the copy of a block it holds
        return (b, jnp.minimum(s, pos_ref[b] // bs), kv)

    def q_block(b, kv, s, pos_ref):
        return (b, kv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, S // bs),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), q_block),
            pl.BlockSpec((1, bs, dh), kv_block),
            pl.BlockSpec((1, bs, dh), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), q_block),
        scratch_shapes=[
            pltpu.VMEM((G, dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_s=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dh), jnp.float32),
        interpret=interpret,
    )(
        jnp.asarray(pos_vec, jnp.int32).reshape(B),
        q,
        cache_k.reshape(B, S, KV * dh),
        cache_v.reshape(B, S, KV * dh),
    )


def flash_decode_ref(q, cache_k, cache_v, pos_vec):
    """jnp oracle: the exact einsum/mask/softmax block this kernel replaces
    (full-S logits materialization and all)."""
    dh = q.shape[-1]
    S = cache_k.shape[1]
    logits = jnp.einsum(
        "bkgd,btkd->bkgt", q.astype(jnp.float32), cache_k.astype(jnp.float32)
    ) * (dh ** -0.5)
    mask = jnp.arange(S)[None, :] <= pos_vec[:, None]  # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", probs, cache_v.astype(jnp.float32))
