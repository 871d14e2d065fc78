"""Sharding rules: parameter/activation PartitionSpecs for the production mesh.

Axis convention (launch/mesh.py): ``("pod", "data", "model")`` multi-pod or
``("data", "model")`` single-pod.  DP runs over ``pod`` x ``data``; TP/EP
over ``model``.  FSDP (ZeRO-3-style) additionally shards the non-TP weight
dim over ``data``.

Rules are name-based over the param pytree paths and *shape-validated*:
an axis is only assigned if the dim divides by the mesh axis size, so the
same rules serve every (arch x mesh) cell (e.g. kv=1 archs silently fall
back to replicated KV heads, batch=1 decode falls back to unsharded batch).
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


DATA_AXES = ("pod", "data")  # flattened DP axes (pod present only multi-pod)


def _sizes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)


def dp_axes(mesh):
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def _dp_entry(mesh):
    dp = dp_axes(mesh)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def validated(spec: P, shape, mesh) -> P:
    """Drop spec entries that name absent axes or don't divide the dim."""
    sizes = _sizes(mesh)
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in sizes)
        prod = 1
        for a in axes:
            prod *= sizes[a]
        if prod > 1 and dim % prod == 0:
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    return P(*out)


def param_spec(path: str, shape, mesh, fsdp: bool) -> P:
    """Partition spec for one parameter, by its tree path.

    Conventions (Megatron-style TP on 'model'):
      embed 'tok' [V, D]      -> (model, fsdp)      vocab-parallel
      lm_head [D, V]          -> (fsdp, model)
      attn wq/wk/wv [D, H*dh] -> (fsdp, model)      head-parallel
      attn wo [H*dh, D]       -> (model, fsdp)
      mlp w_gate/up [D, F]    -> (fsdp, model)
      mlp w_down [F, D]       -> (model, fsdp)
      moe experts [E, D, F]   -> (None, fsdp, model) hidden-parallel per expert
      ssm in/out projections  -> (fsdp, model) / (model, fsdp)
      router / norms / scalars-> replicated
    Leading scan axes ([L], [G, k], [E]) are skipped automatically: rules
    match on the *trailing* dims.
    """
    f = _dp_entry(mesh) if fsdp else None
    name = path.split("/")[-1]

    def trail(spec_tail):
        pad = len(shape) - len(spec_tail)
        if pad < 0:
            spec_tail = spec_tail[-len(shape):]
            pad = 0
        return validated(P(*([None] * pad + list(spec_tail))), shape, mesh)

    if name == "tok":  # embedding [V, D]
        if os.environ.get("REPRO_EMBED_REPLICATED") == "1":
            return trail([None, None])
        return trail(["model", f])
    if name == "lm_head":
        return trail([f, "model"])
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "in_proj", "proj"):
        spec = trail([f, "model"])
        if (
            os.environ.get("REPRO_SHARD_FALLBACK") == "1"
            and spec[-1] is None
            and len(shape) >= 2
        ):
            # output dim doesn't divide the model axis (e.g. mamba2's
            # in_proj [768, 3608]): fall back to contraction-dim TP —
            # shards the matmul K dim, psum per projection, instead of
            # replicating the whole layer across the model axis.
            return trail(["model", f])
        return spec
    if name in ("wo", "w_down", "out_proj"):
        spec = trail(["model", f])
        if (
            os.environ.get("REPRO_SHARD_FALLBACK") == "1"
            and spec[-2] is None
            and len(shape) >= 2
        ):
            return trail([f, "model"])
        return spec
    return trail([None] * len(shape))


def params_shardings(params, mesh, fsdp: bool):
    """NamedSharding pytree for a parameter pytree (works on SDS trees)."""

    def one(path, leaf):
        keys = "/".join(_key_str(k) for k in path)
        spec = param_spec(keys, leaf.shape, mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def _key_str(k) -> str:
    for attr in ("key", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def replicated(mesh):
    return NamedSharding(mesh, P())


def batch_spec(shape, mesh) -> P:
    """Token batches [B, T] / [B, T, D]: batch dim over all DP axes."""
    return validated(P(_dp_entry(mesh)), shape, mesh)


def cache_spec(shape, mesh) -> P:
    """KV caches [..., B, S, KV, dh]: batch over DP, seq over model.

    Sequence-sharding the cache ("SP for decode") keeps 500k-token caches
    distributed even when KV-head count < model-axis size (kv=1 archs);
    validation drops whichever axis doesn't divide.
    """
    pad = len(shape) - 4
    return validated(
        P(*([None] * pad), _dp_entry(mesh), "model", None, None), shape, mesh
    )


# ---------------------------------------------------------------------------
# In-graph activation constraints
# ---------------------------------------------------------------------------


ACT_SPEC = P(("pod", "data"), None, None)         # residual stream [B, T, D]
SEQ_SPEC = P(("pod", "data"), "model", None)      # sequence-parallel variant


def maybe_constrain(x, spec: P):
    """Apply a sharding constraint if tracing under a (sized) mesh context.

    Outside any mesh (CPU unit tests) this is an identity, which keeps the
    model code mesh-agnostic.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    return jax.lax.with_sharding_constraint(x, validated(spec, x.shape, mesh))
