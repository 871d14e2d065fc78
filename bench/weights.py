"""Weights of a dense GQA transformer, made from the run's seed.

The benchmark makes the weights itself, so that the reference can make
the very same ones without taking anything from the program.  Every leaf
is drawn from its own key, ``fold_in(seed key, crc32(leaf path))``, and
the leaf of layer ``l`` from ``fold_in(that key, l)``: the whole stack is
one jitted call on the device for the program, and one layer at a time
for the reference.  The seed's key is an argument of those calls, so one
compiled program serves every seed.  Values are drawn in float32 and
stored in the served type (bfloat16).

The pytree layout is the program's (``repro.models.transformer``):
``embed.tok``, ``final_norm``, ``head.lm_head`` (untied only) and the
layer stack under ``layers``.
"""
from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp

from flops import dims

SERVED = jnp.bfloat16


def seed_key(seed: int):
    """A key from a seed of any size (PRNGKey alone keeps 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def layer_leaves(cfg: Dict) -> Dict[str, tuple]:
    """Per-layer leaves: path -> (shape, kind, scale)."""
    m = dims(cfg)
    d, h, kv, dh, f = m["d"], m["h"], m["kv"], m["dh"], m["f"]
    leaves = {
        "ln1": ((d,), "norm", 0.05),
        "ln2": ((d,), "norm", 0.05),
        "attn/wq": ((d, h * dh), "normal", d ** -0.5),
        "attn/wk": ((d, kv * dh), "normal", d ** -0.5),
        "attn/wv": ((d, kv * dh), "normal", d ** -0.5),
        "attn/wo": ((h * dh, d), "normal", (h * dh) ** -0.5),
        "mlp/w_gate": ((d, f), "normal", d ** -0.5),
        "mlp/w_up": ((d, f), "normal", d ** -0.5),
        "mlp/w_down": ((f, d), "normal", f ** -0.5),
    }
    if cfg.get("qkv_bias"):
        leaves["attn/bq"] = ((h * dh,), "normal", 0.05)
        leaves["attn/bk"] = ((kv * dh,), "normal", 0.05)
        leaves["attn/bv"] = ((kv * dh,), "normal", 0.05)
    return leaves


def global_leaves(cfg: Dict) -> Dict[str, tuple]:
    m = dims(cfg)
    leaves = {
        "embed/tok": ((m["v"], m["d"]), "normal", m["d"] ** -0.5),
        "final_norm": ((m["d"],), "norm", 0.05),
    }
    if not cfg.get("tie_word_embeddings"):
        leaves["head/lm_head"] = ((m["d"], m["v"]), "normal", m["d"] ** -0.5)
    return leaves


def _draw(key, shape, kind, scale):
    z = jax.random.normal(key, shape, jnp.float32)
    out = 1.0 + scale * z if kind == "norm" else scale * z
    return out.astype(SERVED)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, value in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return out


def make_layer(cfg: Dict, key, layer) -> Dict:
    """Layer ``layer``'s weights (nested like one slice of the stack)."""
    flat = {
        path: _draw(jax.random.fold_in(_leaf_key(key, "layers/" + path), layer),
                    shape, kind, scale)
        for path, (shape, kind, scale) in layer_leaves(cfg).items()
    }
    return _nest(flat)


def make_globals(cfg: Dict, key) -> Dict:
    flat = {
        path: _draw(_leaf_key(key, path), shape, kind, scale)
        for path, (shape, kind, scale) in global_leaves(cfg).items()
    }
    return _nest(flat)


def make_params(cfg: Dict, key) -> Dict:
    """The whole model in the program's layout (traceable)."""
    n = dims(cfg)["layers"]
    stack = jax.vmap(lambda l: make_layer(cfg, key, l))(jnp.arange(n))
    return dict(make_globals(cfg, key), layers=stack)


def params_from_seed(cfg: Dict, seed: int) -> Dict:
    """The whole model on the device, in one jitted call."""
    return jax.jit(lambda k: make_params(cfg, k))(seed_key(seed))
