"""Operations and bytes of the work a cell asks for, from shapes alone.

These count the *logical* work: the matrix product a projection computes,
whatever implements it (an MXU matmul, a bit-stream emulation on the VPU,
or an analog-array emulation).  A kernel's roofline share is then the
least time the chip needs for that logical work over the time the kernel
took, so moving work into or out of a kernel changes the time and never
the count.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

BF16_BYTES = 2


def dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "d": d,
        "h": h,
        "kv": int(cfg["num_key_value_heads"]),
        "dh": int(cfg.get("head_dim", d // h)),
        "f": int(cfg["intermediate_size"]),
        "v": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
    }


def projections(cfg: Dict) -> Tuple[List[Tuple[str, int, int]], Tuple[str, int, int]]:
    """(per-layer [(site, K, N)], lm-head (site, K, N)) of a dense GQA
    transformer."""
    m = dims(cfg)
    d, h, kv, dh, f = m["d"], m["h"], m["kv"], m["dh"], m["f"]
    layer = [
        ("attn_q", d, h * dh),
        ("attn_k", d, kv * dh),
        ("attn_v", d, kv * dh),
        ("attn_o", h * dh, d),
        ("mlp_gate", d, f),
        ("mlp_up", d, f),
        ("mlp_down", f, d),
    ]
    return layer, ("lm_head", d, m["v"])


def matmul_params(cfg: Dict) -> int:
    """Weights that take part in a matrix product per token: every
    projection of every layer and the LM head (tied or not)."""
    layer, (_, hk, hn) = projections(cfg)
    return dims(cfg)["layers"] * sum(k * n for _, k, n in layer) + hk * hn


def attention_flops(cfg: Dict, context: int) -> float:
    """Forward flops of score and value products for one query token that
    attends to ``context`` positions, over all layers."""
    m = dims(cfg)
    return 4.0 * m["layers"] * m["h"] * m["dh"] * context


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward and backward flops per trained token of a causal sequence
    of ``seq`` tokens (3x the forward; recomputation is not counted)."""
    mean_context = (seq + 1) / 2.0
    return 3.0 * (2.0 * matmul_params(cfg) + attention_flops(cfg, mean_context))


def serve_flops(cfg: Dict, contexts) -> float:
    """Forward flops of serving one token at each of ``contexts`` (the
    number of positions each token attends to)."""
    mp = matmul_params(cfg)
    return sum(2.0 * mp + attention_flops(cfg, c) for c in contexts)


def matmul_roofline_s(m: int, k: int, n: int, peaks: Dict,
                      elem_bytes: int = BF16_BYTES) -> float:
    """Least time for an [m, k] @ [k, n] product: operations at the bf16
    peak, or operands and result at ``elem_bytes`` over peak bandwidth."""
    flops = 2.0 * m * k * n
    nbytes = float(elem_bytes) * (m * k + k * n + m * n)
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def model_matmuls_roofline_s(cfg: Dict, rows: int, peaks: Dict,
                             with_head: bool = True) -> float:
    """Roofline time of every projection of one forward over ``rows``
    token rows (each projection one call)."""
    layer, head = projections(cfg)
    t = dims(cfg)["layers"] * sum(
        matmul_roofline_s(rows, k, n, peaks) for _, k, n in layer
    )
    if with_head:
        t += matmul_roofline_s(rows, head[1], head[2], peaks)
    return t


def flash_decode_roofline_s(cfg: Dict, positions, peaks: Dict,
                            elem_bytes: int = BF16_BYTES) -> float:
    """Least time of one decode-attention call per layer over all layers:
    each row reads the K and V of positions 0..pos and its query, and
    writes its output."""
    m = dims(cfg)
    row_kv = 2 * m["kv"] * m["dh"] * elem_bytes
    qo = 2 * m["h"] * m["dh"] * elem_bytes
    nbytes = sum((p + 1) * row_kv + qo for p in positions)
    flops = sum(4.0 * m["h"] * m["dh"] * (p + 1) for p in positions)
    per_layer = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return m["layers"] * per_layer
