"""Plain reference of a dense GQA transformer and of the emulated
hardware it is served or trained for.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the architecture's published description (RMSNorm, rotary
embeddings on halves, grouped-query causal attention, SwiGLU, optional
q/k/v bias, tied or untied head) and from the hardware's definition.  It
imports nothing of the program and takes nothing the program made: the
weights come from ``weights.py`` and the seed, the stochastic streams
from the documented seeding rule (``stream_key``).

``cast`` is applied to the operands of every product and attention
score.  The reference leaves them as they are; the control rounds them,
and their cotangents in the backward, to float8 (e4m3) under per-tensor
scales: the precision below the configuration's bfloat16.

Emulated products:

* ``sc`` — stochastic computing, split-unipolar.  Values are scaled by
  ``gain / max|.|`` into probabilities; the four unipolar planes become
  bit streams by comparing each probability with a uniform draw (one
  draw per stream bit shared by all activation ports, one per weight row
  and bit); each output bit is the OR over the contraction of the AND
  products, counted over the stream.  The positive output plane ORs
  {x+ w+} and {x- w-}, the negative {x+ w-} and {x- w+}.  A 0/1 matrix
  product counts the ANDs exactly, so ``OR = (count > 0)``.  The backward
  is the paper's proxy: ``(1 - e^-z+) - (1 - e^-z-)`` of the unipolar
  half-sums.
* ``exact`` — the plain product.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from flops import dims

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def no_cast(t):
    return t


def _fp8_round(t):
    """Round to float8 e4m3 under one per-tensor scale (max |t| to 448),
    as a float8 datapath holds a tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    return (t / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@jax.custom_vjp
def fp8_cast(t):
    return _fp8_round(t)


# the backward holds the cotangent in float8 too, under its own scale
fp8_cast.defvjp(lambda t: (_fp8_round(t), None), lambda _, g: (_fp8_round(g),))


def stream_key(step_key, layer: Optional[int], site: str):
    """The key of a projection's bit streams: the step's key, folded with
    the layer index (the LM head uses 2**20), then with crc32 of the
    projection's site name."""
    k = jax.random.fold_in(step_key, 2 ** 20 if layer is None else layer)
    return jax.random.fold_in(k, zlib.crc32(site.encode()) & 0x7FFFFFFF)


def _mm(a, b):
    return jnp.dot(a, b, precision=HI, preferred_element_type=F32)


def _count(a01, b01):
    """Exact count of AND products of two 0/1 matrices."""
    return jnp.dot(a01.astype(jnp.bfloat16), b01.astype(jnp.bfloat16),
                   preferred_element_type=F32)


def _scale(t):
    return jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(t)), 1e-6))


def _planes(t):
    return jnp.maximum(t, 0.0), jnp.maximum(-t, 0.0)


def sc_forward(x2, w, key, bits: int, gain: float, cast=no_cast):
    x2, w = cast(x2), cast(w)
    sx, sw = _scale(x2), _scale(w)
    xs, ws = x2 * (gain / sx), w * (gain / sw)
    K = w.shape[0]
    kx, kw = jax.random.split(key)
    ux = jax.random.uniform(kx, (1, bits), dtype=F32)[0]
    uw = jax.random.uniform(kw, (2 * K, bits), dtype=F32)

    # a unipolar plane's bit is (plane > u); for u in [0, 1) that is
    # (+value > u) for the positive plane and (-value > u) for the
    # negative one, clipping to [0, 1] included.  Activation ports
    # 0..K-1 carry x+, K..2K-1 carry x-; the positive output plane pairs
    # them with w+ and w-, the negative one with w- and w+.
    def bit(l, acc):
        xp, xn = xs > ux[l], -xs > ux[l]
        u_top, u_bot = uw[:K, l][:, None], uw[K:, l][:, None]
        pos = _count(xp, ws > u_top) + _count(xn, -ws > u_bot)
        neg = _count(xp, -ws > u_top) + _count(xn, ws > u_bot)
        return acc + (pos > 0.5).astype(F32) - (neg > 0.5).astype(F32)

    acc = jax.lax.fori_loop(
        0, bits, bit, jnp.zeros((x2.shape[0], w.shape[1]), F32)
    )
    return acc / bits * (sx * sw / (gain * gain))


def sc_proxy(x2, w, gain: float, cast=no_cast):
    x2, w = cast(x2), cast(w)
    sx, sw = _scale(x2), _scale(w)
    xp, xn = _planes(x2 * (gain / sx))
    wp, wn = _planes(w * (gain / sw))
    z_pos = _mm(xp, wp) + _mm(xn, wn)
    z_neg = _mm(xp, wn) + _mm(xn, wp)
    return ((1.0 - jnp.exp(-z_pos)) - (1.0 - jnp.exp(-z_neg))) * (
        sx * sw / (gain * gain)
    )


def make_sc_product(bits: int, gain: float, cast=no_cast):
    """Emulated forward, proxy backward (the paper's MODEL mode)."""

    @jax.custom_vjp
    def product(x2, w, key):
        return sc_forward(x2, w, key, bits, gain, cast)

    def fwd(x2, w, key):
        return product(x2, w, key), (x2, w)

    def bwd(res, g):
        x2, w = res
        _, vjp = jax.vjp(lambda a, b: sc_proxy(a, b, gain, cast), x2, w)
        gx, gw = vjp(g)
        return gx, gw, None

    product.defvjp(fwd, bwd)
    return product


def exact_forward(x2, w, cast=no_cast):
    return _mm(cast(x2), cast(w))


def product_fn(traffic: Dict, cast=no_cast) -> Callable:
    """``fn(x2 [M, K], w [K, N], key) -> [M, N]`` for the traffic's
    backend (the key is used by stochastic backends only)."""
    backend = traffic["backend"]
    if backend == "sc":
        p = make_sc_product(int(traffic["sc_bits"]), float(traffic["sc_gain"]), cast)
        return p
    if backend == "exact":
        return lambda x2, w, key: exact_forward(x2, w, cast)
    raise ValueError(f"the reference has no emulation of backend {backend!r}")


# ---------------------------------------------------------------------------
# The transformer
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [..., T, H, dh]; rotation of the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def layer_forward(x, p, cfg: Dict, product: Callable, keys: Dict, cast=no_cast):
    """One block over ``x [B, T, d]`` (float32)."""
    m = dims(cfg)
    B, T, d = x.shape
    H, KV, dh = m["h"], m["kv"], m["dh"]
    eps = float(cfg["rms_norm_eps"])
    f32 = lambda t: t.astype(F32)

    def proj(h, w, site, b=None):
        y = product(h.reshape(B * T, -1), f32(w), keys.get(site)).reshape(B, T, -1)
        return y if b is None else y + f32(b)

    a = p["attn"]
    h = rmsnorm(x, f32(p["ln1"]), eps)
    q = proj(h, a["wq"], "attn_q", a.get("bq")).reshape(B, T, H, dh)
    k = proj(h, a["wk"], "attn_k", a.get("bk")).reshape(B, T, KV, dh)
    v = proj(h, a["wv"], "attn_v", a.get("bv")).reshape(B, T, KV, dh)
    pos = jnp.arange(T)
    theta = float(cfg["rope_theta"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = H // KV
    qg = cast(q).reshape(B, T, KV, g, dh)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, cast(k), precision=HI) * dh ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    att = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", cast(att), cast(v), precision=HI)
    x = x + proj(o.reshape(B, T, H * dh), a["wo"], "attn_o")
    h = rmsnorm(x, f32(p["ln2"]), eps)
    mm = p["mlp"]
    gate = proj(h, mm["w_gate"], "mlp_gate")
    up = proj(h, mm["w_up"], "mlp_up")
    return x + proj(jax.nn.silu(gate) * up, mm["w_down"], "mlp_down")


def head_weight(params: Dict, cfg: Dict):
    if cfg.get("tie_word_embeddings"):
        return params["embed"]["tok"].astype(F32).T
    return params["head"]["lm_head"].astype(F32)


def forward(params: Dict, tokens, cfg: Dict, product: Callable, step_key,
            cast=no_cast):
    """Logits [B, T, V] of the whole model (params in the program's
    layout, the layer stack indexed per layer)."""
    n = dims(cfg)["layers"]
    x = params["embed"]["tok"].astype(F32)[tokens]
    sites = ("attn_q", "attn_k", "attn_v", "attn_o", "mlp_gate", "mlp_up", "mlp_down")
    for l in range(n):
        p = jax.tree_util.tree_map(lambda t: t[l], params["layers"])
        keys = {s: stream_key(step_key, l, s) for s in sites}
        x = layer_forward(x, p, cfg, product, keys, cast)
    x = rmsnorm(x, params["final_norm"].astype(F32), float(cfg["rms_norm_eps"]))
    B, T, d = x.shape
    w = head_weight(params, cfg)
    return product(x.reshape(B * T, d), w, stream_key(step_key, None, "lm_head")).reshape(B, T, -1)


def lm_loss(logits, labels):
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - ll)
