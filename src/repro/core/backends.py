"""Bit-accurate forward emulation of the approximate hardware (Sec. 2/3).

These are the *expensive* forward paths (paper Tab. 1: 2-86x the cost of
an FMA).  They are used (a) throughout MODEL-mode training / fine-tuning,
(b) on calibration batches in INJECT mode, and (c) for validation.

Each emulator is a standalone ``(x, w, params, rng)`` function dispatching
to a Pallas TPU kernel via ``repro.kernels.ops`` for the blocked hot loop;
``repro.kernels.ref`` holds the pure-jnp oracle the kernels are validated
against.  The value-domain scaling (per-tensor dynamic scale, split-
unipolar planes) lives here so kernels stay pure probability/integer-
domain contractions.

This module also *defines the built-in backend registry entries*: at the
bottom, each hardware target is bundled with its params dataclass, proxy
activation and kernel handles into a :class:`~repro.core.registry.
BackendSpec` and registered.  Everything upstream (``proxy``,
``injection``, ``calibration``, ``dense()``) dispatches through that
registry — adding a backend means registering one more spec here (or in
your own module), not editing dispatch chains.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AnalogParams,
    ApproxConfig,
    ApproxMultParams,
    Backend,
    LogMultParams,
    SCParams,
)
from repro.core import proxy as proxy_lib
from repro.core import registry
from repro.core.proxy import row_scale, split_signed, tensor_scale
from repro.core.registry import BackendSpec, split_unipolar_contract
from repro.kernels import ops as kops


def fake_quant_unipolar(x, bits: int):
    """Round a [0,1] tensor to ``bits`` levels (straight-through estimator)."""
    levels = (1 << bits) - 1
    q = jnp.round(x * levels) / levels
    return x + jax.lax.stop_gradient(q - x)


def emulate(x, w, cfg: ApproxConfig, rng, backend: Optional[Backend] = None) -> jax.Array:
    """Bit-accurate forward of ``x @ w`` on the configured hardware.

    Dispatches through the backend registry; ``backend`` overrides
    ``cfg.backend`` for per-site heterogeneous configs.
    """
    backend = backend if backend is not None else cfg.backend
    spec = registry.get(backend)
    return spec.emulate(x, w, cfg.params_for(backend), rng)


def _emulate_exact(x, w, p, rng):
    del p, rng
    return x @ w


# ---------------------------------------------------------------------------
# Stochastic computing: split-unipolar streams, AND multiply, OR accumulate
# ---------------------------------------------------------------------------


def _emulate_sc(x, w, p: SCParams, rng):
    g = p.gain
    sx = tensor_scale(x)
    sw = tensor_scale(w)
    xp, xn = split_signed(x * (g / sx))
    wp, wn = split_signed(w * (g / sw))
    # probabilities must be in [0, 1]
    xp, xn, wp, wn = (jnp.clip(t, 0.0, 1.0) for t in (xp, xn, wp, wn))

    # Split-unipolar with signed inputs: the positive-output OR tree
    # accumulates the {xp*wp} U {xn*wn} product streams, the negative tree
    # {xp*wn} U {xn*wp} — one OR accumulation per polarity over 2K ports
    # (the paper's "2x computation" for split-unipolar, Sec. 3).  Both
    # polarities consume the SAME generator sequences (shared hardware).
    kx, kw = jax.random.split(rng)
    r = split_unipolar_contract(
        (xp, xn), (wp, wn), lambda a, b: kops.sc_matmul(a, b, p.bits, kx, kw)
    )
    rescale = (sx * sw) / (g * g)
    return (r * rescale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Analog arrays: operand quantization + per-array ADC partial-sum quantization
# ---------------------------------------------------------------------------


def _emulate_analog(x, w, p: AnalogParams, rng):
    del rng
    sx = tensor_scale(x)
    sw = tensor_scale(w)
    xp, xn = split_signed(x / sx)
    wp, wn = split_signed(w / sw)
    xp = fake_quant_unipolar(xp, p.input_bits)
    xn = fake_quant_unipolar(xn, p.input_bits)
    wp = fake_quant_unipolar(wp, p.weight_bits)
    wn = fake_quant_unipolar(wn, p.weight_bits)

    # One physical accumulation per polarity over the concatenated 2K
    # unipolar ports (arrays of `array_size` see a contiguous slice of the
    # combined product stream), matching the proxy's single clamp per half.
    out = split_unipolar_contract(
        (xp, xn), (wp, wn),
        lambda a, b: kops.analog_matmul(a, b, p.array_size, p.adc_bits, p.adc_range),
    )
    return (out * (sx * sw)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Multiplier-error backends: integer operands, exact accumulation, error
# per multiply — behavioural truncated multiplier and Mitchell log multiply
# ---------------------------------------------------------------------------


def _int_operand_quantize(x, w, bits: int):
    """Per-token dynamic quantization to signed integer magnitudes, plus
    the value-domain prescale that undoes it after the contraction."""
    levels = (1 << bits) - 1
    sx = row_scale(x)  # per-token dynamic quantization: batch-invariant
    sw = tensor_scale(w)  # serving (see row_scale's docstring)
    xi = jnp.round(jnp.clip(x / sx, -1.0, 1.0) * levels)
    wi = jnp.round(jnp.clip(w / sw, -1.0, 1.0) * levels)
    return xi, wi, sx * sw / (levels * levels)


def _int_operand_emulate(x, w, bits: int, matmul):
    """Shared scaffolding for multiplier-error backends: scale to signed
    integer magnitudes, contract through ``matmul``, rescale.

    Forward value only — like the SC/analog emulators, gradients come
    from the registry proxy via ``injection``'s custom_vjp (round() has
    zero gradient a.e., so differentiating this directly is meaningless).
    Keeping the forward free of straight-through arithmetic is what lets
    the fused kernels reproduce it bit-for-bit."""
    xi, wi, prescale = _int_operand_quantize(x, w, bits)
    acc = matmul(xi.reshape(-1, x.shape[-1]), wi)
    out = acc.reshape(x.shape[:-1] + (w.shape[-1],)) * prescale
    return out.astype(x.dtype)


def _emulate_approx_mult(x, w, p: ApproxMultParams, rng):
    del rng
    return _int_operand_emulate(
        x, w, p.bits, lambda a, b: kops.approx_mult_matmul(a, b, p.bits, p.perforate)
    )


def _emulate_log_mult(x, w, p: LogMultParams, rng):
    del rng
    return _int_operand_emulate(x, w, p.bits, kops.log_matmul)


# ---------------------------------------------------------------------------
# Fused MODEL-mode emulators: the matmul (both unipolar planes where the
# backend has two), rescale and cast in one kernel pass, then the
# chip/calibration epilogue (the serving hot path).  Value-domain scaling
# mirrors the composed emulators above op for op; the kernels replicate the
# composed accumulation order, so fused == composed bit for bit.
# ---------------------------------------------------------------------------


def _fused_int_operand(x, w, bits: int, fused_matmul, epi: dict):
    xi, wi, prescale = _int_operand_quantize(x, w, bits)
    y = fused_matmul(
        xi.reshape(-1, x.shape[-1]), wi, prescale.reshape(-1, 1), epi, x.dtype
    )
    return y.reshape(x.shape[:-1] + (w.shape[-1],))


def _fused_emulate_approx_mult(x, w, p: ApproxMultParams, rng, epi):
    del rng
    return _fused_int_operand(
        x, w, p.bits,
        lambda a, b, pre, e, dt: kops.approx_mult_matmul_fused(
            a, b, p.bits, p.perforate, pre, e, dt
        ),
        epi,
    )


def _fused_emulate_log_mult(x, w, p: LogMultParams, rng, epi):
    del rng
    return _fused_int_operand(
        x, w, p.bits,
        lambda a, b, pre, e, dt: kops.log_matmul_fused(a, b, pre, e, dt),
        epi,
    )


def _fused_emulate_sc(x, w, p: SCParams, rng, epi):
    g = p.gain
    sx = tensor_scale(x)
    sw = tensor_scale(w)
    xp, xn = split_signed(x * (g / sx))
    wp, wn = split_signed(w * (g / sw))
    xp, xn, wp, wn = (jnp.clip(t, 0.0, 1.0) for t in (xp, xn, wp, wn))
    kx, kw = jax.random.split(rng)
    K = xp.shape[-1]
    xcat = jnp.concatenate([xp, xn], axis=-1).reshape(-1, 2 * K)
    w_pos = jnp.concatenate([wp, wn], axis=0)
    w_neg = jnp.concatenate([wn, wp], axis=0)
    rescale = (sx * sw) / (g * g)
    y = kops.sc_matmul_fused(
        xcat, w_pos, w_neg, p.bits, kx, kw, rescale, epi, x.dtype
    )
    return y.reshape(x.shape[:-1] + (w.shape[-1],))


def _fused_emulate_analog(x, w, p: AnalogParams, rng, epi):
    del rng
    sx = tensor_scale(x)
    sw = tensor_scale(w)
    xp, xn = split_signed(x / sx)
    wp, wn = split_signed(w / sw)
    xp = fake_quant_unipolar(xp, p.input_bits)
    xn = fake_quant_unipolar(xn, p.input_bits)
    wp = fake_quant_unipolar(wp, p.weight_bits)
    wn = fake_quant_unipolar(wn, p.weight_bits)
    K = xp.shape[-1]
    xcat = jnp.concatenate([xp, xn], axis=-1).reshape(-1, 2 * K)
    w_pos = jnp.concatenate([wp, wn], axis=0)
    w_neg = jnp.concatenate([wn, wp], axis=0)
    y = kops.analog_matmul_fused(
        xcat, w_pos, w_neg, p.array_size, p.adc_bits, p.adc_range,
        sx * sw, epi, x.dtype,
    )
    return y.reshape(x.shape[:-1] + (w.shape[-1],))


# ---------------------------------------------------------------------------
# Parametric deployment-energy models (relative energy per MAC; one exact
# digital MAC = 1.0).  These are the paper's Tab. 1 relative op costs made
# parametric in each backend's hardware knobs, consumed by
# repro.search.costmodel to price a site->backend assignment in
# joules-equivalents.  Constants are calibrated to the usual orderings in
# the approximate-computing literature (SC energy grows linearly with
# stream length and split-unipolar doubles the streams; a truncated
# multiplier scales ~quadratically with operand width and saves ~8% per
# perforated partial-product row; a Mitchell multiplier replaces the
# multiply array with shift/add; an analog MAC is nearly free but pays an
# amortized share of its ADC, whose energy grows exponentially in
# resolution) — monotone in every knob, which is what the search needs.
# ---------------------------------------------------------------------------

_SC_BIT_CYCLE = 0.02       # AND+OR per stream bit-cycle vs one exact MAC
_SC_RNG_OVERHEAD = 0.10    # stream generation (shared LFSRs, amortized)
_ANALOG_MAC = 0.005        # crossbar current-summing MAC
_ANALOG_ADC_UNIT = 0.004   # per-conversion unit: * bits * 2^bits / array
_LOG_MULT_SCALE = 0.30     # shift/add vs multiply array, at 8-bit operands
_APPROX_MULT_PERFORATE_SAVE = 0.08  # energy saved per dropped PP row


def _energy_sc(p: SCParams) -> float:
    # split-unipolar signed operands: 2x streams (paper Sec. 3)
    return _SC_RNG_OVERHEAD + _SC_BIT_CYCLE * 2 * p.bits


def _energy_analog(p: AnalogParams) -> float:
    adc = _ANALOG_ADC_UNIT * p.adc_bits * (1 << p.adc_bits) / max(p.array_size, 1)
    # operand DACs scale linearly in resolution (minor next to the ADC)
    dac = 0.001 * (p.input_bits + p.weight_bits) / 16.0
    return _ANALOG_MAC + adc + dac


def _energy_approx_mult(p: ApproxMultParams) -> float:
    full = (p.bits / 8.0) ** 2  # multiplier array area/energy ~ bits^2
    return max(full * (1.0 - _APPROX_MULT_PERFORATE_SAVE * p.perforate), 1e-3)


def _energy_log_mult(p: LogMultParams) -> float:
    return _LOG_MULT_SCALE * p.bits / 8.0


# ---------------------------------------------------------------------------
# Built-in backend specs
# ---------------------------------------------------------------------------

registry.register(BackendSpec(
    name=Backend.EXACT.value,
    params_cls=type(None),
    emulate=_emulate_exact,
    proxy_forward=proxy_lib.identity_proxy,
    calib_degree=0,
    energy=lambda p: 1.0,
))

registry.register(BackendSpec(
    name=Backend.SC.value,
    params_cls=SCParams,
    emulate=_emulate_sc,
    proxy_forward=proxy_lib.sc_proxy,
    kernels=kops.KERNELS["sc"],
    energy=_energy_sc,
    fused_emulate=_fused_emulate_sc,
))

registry.register(BackendSpec(
    name=Backend.ANALOG.value,
    params_cls=AnalogParams,
    emulate=_emulate_analog,
    proxy_forward=proxy_lib.analog_proxy,
    # Type 2 (paper): plain matmul on non-calibration INJECT batches —
    # saturation only enters via fine-tuning — and scalar (degree-0) stats.
    fast_forward=proxy_lib.identity_proxy,
    calib_degree=0,
    kernels=kops.KERNELS["analog"],
    energy=_energy_analog,
    fused_emulate=_fused_emulate_analog,
))

registry.register(BackendSpec(
    name=Backend.APPROX_MULT.value,
    params_cls=ApproxMultParams,
    emulate=_emulate_approx_mult,
    proxy_forward=proxy_lib.identity_proxy,
    kernels=kops.KERNELS["approx_mult"],
    energy=_energy_approx_mult,
    fused_emulate=_fused_emulate_approx_mult,
))

registry.register(BackendSpec(
    name=Backend.LOG_MULT.value,
    params_cls=LogMultParams,
    emulate=_emulate_log_mult,
    proxy_forward=proxy_lib.identity_proxy,
    kernels=kops.KERNELS["log_mult"],
    energy=_energy_log_mult,
    fused_emulate=_fused_emulate_log_mult,
))
