"""Jit-friendly dispatch wrappers around the emulation kernels.

Each wrapper pairs a Pallas kernel with its pure-jnp oracle and selects
the implementation per call; backend specs in the registry
(:mod:`repro.core.registry`) carry these wrappers as their kernel
handles, so benchmarks and tooling can reach a backend's hot loop by
name (``registry.get(b).kernels["matmul"]``) without knowing the module
layout.

``REPRO_KERNELS`` env var selects the implementation:

* ``auto`` (default) — Pallas on TPU, pure-jnp reference on CPU (the
  reference is itself K-chunked and jit-compiled; interpret-mode Pallas is
  orders of magnitude slower under vmap/scan so it is reserved for the
  correctness tests).
* ``pallas``      — force Pallas (compiled on TPU, interpret on CPU).
* ``ref``         — force the pure-jnp oracle.

``REPRO_FUSED`` selects the *default* for the fused MODEL-mode hot path
(epilogue-fused matmuls + flash decode attention); serving code can
override per engine.  ``1``/``true``/``on`` enables it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels import analog_matmul as _analog
from repro.kernels import approx_mult as _amult
from repro.kernels import flash_decode as _flash
from repro.kernels import log_matmul as _log
from repro.kernels import sc_matmul as _sc
from repro.kernels.epilogue import apply_epilogue


def _impl() -> str:
    mode = os.environ.get("REPRO_KERNELS", "auto")
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return mode


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_default() -> bool:
    """Process-wide default for the fused decode hot path (``REPRO_FUSED``)."""
    return os.environ.get("REPRO_FUSED", "").lower() in ("1", "true", "on")


def analog_matmul(x, w, array_size: int, adc_bits: int, adc_range: float):
    """Unipolar [M,K] @ [K,N] with per-array ADC quantization."""
    if _impl() == "pallas":
        return _analog.analog_matmul(
            x, w, array_size, adc_bits, adc_range, interpret=_interpret()
        )
    return kref.analog_matmul_ref(
        x.astype(jnp.float32), w.astype(jnp.float32), array_size, adc_bits, adc_range
    )


def approx_mult_matmul(x, w, mult_bits: int, perforate: int):
    """Integer-valued [M,K] @ [K,N] through the approximate multiplier."""
    if _impl() == "pallas":
        return _amult.approx_mult_matmul(
            x, w, mult_bits, perforate, interpret=_interpret()
        )
    return kref.approx_mult_matmul_ref(
        x.astype(jnp.float32), w.astype(jnp.float32), mult_bits, perforate
    )


def log_matmul(x, w):
    """Integer-valued [M,K] @ [K,N] through the Mitchell log multiplier."""
    if _impl() == "pallas":
        return _log.log_matmul(x, w, interpret=_interpret())
    return kref.log_matmul_ref(x.astype(jnp.float32), w.astype(jnp.float32))


def _sc_streams(x, ws, n_bits: int, rng_x, rng_w):
    """Pack SC streams for an activation plane [M, K] and weight planes
    [K, N]: one activation-side generator sequence shared by all K ports,
    an independent generator per weight row — the draws
    ``ref.sc_matmul_ref`` makes, so the kernel and the oracle consume
    identical packed words."""
    K = x.shape[-1]
    ux = jnp.broadcast_to(
        jax.random.uniform(rng_x, (1, n_bits), dtype=jnp.float32), (K, n_bits)
    )
    uw = jax.random.uniform(rng_w, (K, n_bits), dtype=jnp.float32)
    xbits = kref.sc_pack_streams(x.astype(jnp.float32), ux)
    return xbits, [
        kref.sc_pack_streams(w.astype(jnp.float32), uw[:, None, :]) for w in ws
    ]


def sc_matmul(xp, wp, n_bits: int, rng_x, rng_w):
    """Probability-domain [M,K] @ [K,N] through packed SC streams."""
    if _impl() != "pallas":
        return kref.sc_matmul_ref(xp, wp, n_bits, rng_x, rng_w)
    xbits, (wbits,) = _sc_streams(xp, [wp], n_bits, rng_x, rng_w)
    return _sc.sc_matmul_packed(xbits, wbits, n_bits, interpret=_interpret())


# ---------------------------------------------------------------------------
# Fused dispatch: one kernel pass for the matmul (both unipolar planes where
# the backend has two), the per-token rescale and the cast; the chip +
# calibration epilogue then runs on the [M, N] result.  The epilogue's row
# scale is a max over the whole output row, which an N-tiled kernel does not
# see; a max chain is order-free, so taking it in a separate pass over the
# written tile gives the composed path's bits.
# ---------------------------------------------------------------------------


def analog_matmul_fused(
    x, w_pos, w_neg, array_size: int, adc_bits: int, adc_range: float,
    prescale, epi: dict, out_dtype,
):
    """Dual-plane unipolar contraction with ADC quantization and rescale in
    one kernel, then the chip/calibration epilogue."""
    if _impl() == "pallas":
        y = _analog.analog_matmul_fused(
            x, w_pos, w_neg, array_size, adc_bits, adc_range,
            prescale, out_dtype, interpret=_interpret(),
        )
    else:
        xf = x.astype(jnp.float32)
        out = kref.analog_matmul_ref(
            xf, w_pos.astype(jnp.float32), array_size, adc_bits, adc_range
        ) - kref.analog_matmul_ref(
            xf, w_neg.astype(jnp.float32), array_size, adc_bits, adc_range
        )
        y = (out * prescale).astype(out_dtype)
    return apply_epilogue(y, **epi)


def approx_mult_matmul_fused(
    x, w, mult_bits: int, perforate: int, prescale, epi: dict, out_dtype
):
    """Approximate-multiplier contraction and rescale in one kernel, then
    the chip/calibration epilogue."""
    if _impl() == "pallas":
        y = _amult.approx_mult_matmul(
            x, w, mult_bits, perforate, prescale=prescale,
            out_dtype=out_dtype, interpret=_interpret(),
        )
    else:
        drop_bits = 2 * perforate
        acc = kref.elementwise_matmul_chunked_ref(
            x.astype(jnp.float32), w.astype(jnp.float32),
            lambda a, b: kref.approx_mul(a, b, drop_bits),
        )
        y = (acc * prescale).astype(out_dtype)
    return apply_epilogue(y, **epi)


def log_matmul_fused(x, w, prescale, epi: dict, out_dtype):
    """Mitchell-multiplier contraction and rescale in one kernel, then the
    chip/calibration epilogue."""
    if _impl() == "pallas":
        y = _log.log_matmul(
            x, w, prescale=prescale, out_dtype=out_dtype,
            interpret=_interpret(),
        )
    else:
        acc = kref.elementwise_matmul_chunked_ref(
            x.astype(jnp.float32), w.astype(jnp.float32), kref.mitchell_mul
        )
        y = (acc * prescale).astype(out_dtype)
    return apply_epilogue(y, **epi)


def sc_matmul_fused(
    xcat, w_pos, w_neg, n_bits: int, rng_x, rng_w, prescale, epi: dict, out_dtype
):
    """Dual-plane SC stream contraction and rescale in one kernel, then the
    chip/calibration epilogue.

    ``xcat``/``w_pos``/``w_neg`` are the concatenated probability planes
    from ``split_unipolar_contract``'s layout; stream generation matches
    the unfused :func:`sc_matmul` draws exactly (same keys, same shapes),
    so the packed words are identical bit for bit.
    """
    xbits, (wp_bits, wn_bits) = _sc_streams(
        xcat, [w_pos, w_neg], n_bits, rng_x, rng_w
    )
    if _impl() == "pallas":
        y = _sc.sc_matmul_packed_fused(
            xbits, wp_bits, wn_bits, n_bits, prescale, out_dtype,
            interpret=_interpret(),
        )
    else:
        r = (
            kref.sc_matmul_packed_chunked_ref(xbits, wp_bits) / n_bits
            - kref.sc_matmul_packed_chunked_ref(xbits, wn_bits) / n_bits
        )
        y = (r * prescale).astype(out_dtype)
    return apply_epilogue(y, **epi)


def flash_decode_attention(q, cache_k, cache_v, pos_vec):
    """Bucketed online-softmax decode attention (``q`` [B,KV,G,dh] against
    ragged caches [B,S,KV,dh] at per-row ``pos_vec``) -> [B,KV,G,dh] f32."""
    if _impl() == "pallas":
        return _flash.flash_decode(
            q, cache_k, cache_v, pos_vec, interpret=_interpret()
        )
    return _flash.flash_decode_ref(q, cache_k, cache_v, pos_vec)


# Named kernel handles, one entry per approximate backend — the registry's
# BackendSpec.kernels values point here.
KERNELS = {
    "sc": {"matmul": sc_matmul, "matmul_fused": sc_matmul_fused},
    "analog": {"matmul": analog_matmul, "matmul_fused": analog_matmul_fused},
    "approx_mult": {
        "matmul": approx_mult_matmul,
        "matmul_fused": approx_mult_matmul_fused,
    },
    "log_mult": {"matmul": log_matmul, "matmul_fused": log_matmul_fused},
}
