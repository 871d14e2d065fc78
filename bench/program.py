"""How the benchmark's data files map onto the program's configuration
objects: the one place that knows the program's configuration API."""
from __future__ import annotations

from typing import Dict

from flops import dims


def model_config(cfg: Dict):
    from repro.configs.base import Family, ModelConfig

    m = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family=Family.DENSE, n_layers=m["layers"],
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["kv"], d_ff=m["f"],
        vocab_size=m["v"], qkv_bias=bool(cfg.get("qkv_bias", False)),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        param_dtype="bfloat16", compute_dtype="bfloat16",
    )


def approx_config(t: Dict):
    from repro.configs.base import ApproxConfig, Backend, SCParams, TrainMode

    return ApproxConfig(
        backend=Backend(t["backend"]), mode=TrainMode(t["mode"]),
        sc=SCParams(bits=int(t.get("sc_bits", 32)), gain=float(t.get("sc_gain", 0.25))),
    )


def program_seed(seed: int) -> int:
    """The program's own seed (its keys hold 32 bits)."""
    return seed % (2 ** 31 - 1)
