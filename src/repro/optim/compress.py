"""Gradient compression for the cross-pod reduction (multi-pod training).

Within a pod, gradients reduce over the ``data`` axis implicitly through
SPMD (ICI-speed, cheap).  *Across pods* the reduction crosses DCI links —
the expensive hop at 1000+ node scale — so the framework exposes
compressed all-reduce primitives to be used inside a ``shard_map`` over
the ``pod`` axis:

* :func:`int8_allreduce`  — per-tensor scaled int8 quantization with error
  feedback (residual carried locally to the next step): 8/32 of the bytes
  on the wire.
* :func:`topk_allreduce`  — magnitude top-k sparsification with error
  feedback.

Error feedback makes both schemes converge like uncompressed SGD/Adam in
expectation: the quantization residual is re-injected next step, so no
gradient information is permanently lost (momentum-style bias vanishes).

:func:`crosspod_reduce` wraps a gradient pytree in the shard_map; it is
the integration point used by the multi-pod trainer (identity on meshes
without a pod axis).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def init_compression_state(params, method: str, dtype=jnp.bfloat16):
    """Error-feedback residual buffers (zero) — only for compressing modes.

    Stored in bf16 by default (half the resident bytes — the residual is
    a noise-scale correction, well inside bf16 range); the reducers
    compute in f32 and round back on write.  Error feedback stays
    convergent: the residual re-injection is unbiased in expectation and
    any bf16 rounding loss is itself re-absorbed into the next residual.
    Pass ``dtype=jnp.float32`` to restore full-precision buffers.
    """
    if method == "none":
        return None
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype), params
    )


# ---------------------------------------------------------------------------
# Primitives (call inside shard_map over the reduction axis)
# ---------------------------------------------------------------------------


def int8_allreduce(g, ef, axis: str) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback int8-quantized mean over ``axis``.

    Returns (mean_of_dequantized, new_error_feedback).  The wire payload is
    the int8 tensor + one f32 scale per tensor (the psum here operates on
    the dequantized values for portability; on real DCI the int8 payload is
    what moves — the dry-run's collective-bytes accounting uses the int8
    size for compressed mode).
    """
    x = g.astype(jnp.float32) + ef.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    deq = q * scale
    new_ef = (x - deq).astype(ef.dtype)
    total = jax.lax.psum(deq, axis)
    n = jax.lax.psum(1, axis)
    return total / n, new_ef


def topk_allreduce(g, ef, frac: float, axis: str) -> Tuple[jax.Array, jax.Array]:
    """Error-feedback magnitude top-k mean over ``axis``."""
    x = (g.astype(jnp.float32) + ef.astype(jnp.float32)).reshape(-1)
    k = max(1, int(x.size * frac))
    thresh = jax.lax.top_k(jnp.abs(x), k)[0][-1]
    kept = jnp.where(jnp.abs(x) >= thresh, x, 0.0)
    new_ef = (x - kept).reshape(g.shape).astype(ef.dtype)
    total = jax.lax.psum(kept, axis)
    n = jax.lax.psum(1, axis)
    return (total / n).reshape(g.shape), new_ef


# ---------------------------------------------------------------------------
# Pytree wrapper
# ---------------------------------------------------------------------------


def crosspod_reduce(
    grads: Any,
    ef_state: Any,
    mesh: Mesh,
    method: str = "none",
    *,
    axis: str = "pod",
):
    """Average a gradient pytree over the ``pod`` mesh axis, compressed.

    Identity when the mesh has no pod axis (single-pod training: SPMD
    already reduced everything).  Gradients enter replicated per pod
    (P() specs relative to the pod axis); compression is exercised
    per-pod-locally with the reduction over ``axis``.
    """
    if axis not in mesh.axis_names or mesh.shape[axis] == 1 or method == "none":
        return grads, ef_state

    def reducer(g, ef):
        if method == "int8":
            return int8_allreduce(g, ef, axis)
        if method.startswith("topk:"):
            return topk_allreduce(g, ef, float(method.split(":", 1)[1]), axis)
        raise ValueError(f"unknown compression {method!r}")

    def body(grads, ef):
        flat_g, tdef = jax.tree_util.tree_flatten(grads)
        flat_e = jax.tree_util.tree_leaves(ef)
        out_g, out_e = [], []
        for g, e in zip(flat_g, flat_e):
            rg, re = reducer(g, e)
            out_g.append(rg.astype(g.dtype))
            out_e.append(re)
        return (
            jax.tree_util.tree_unflatten(tdef, out_g),
            jax.tree_util.tree_unflatten(tdef, out_e),
        )

    specs = jax.tree_util.tree_map(lambda _: P(), grads)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(specs, specs), out_specs=(specs, specs),
        check_vma=False,
    )
    return fn(grads, ef_state)
