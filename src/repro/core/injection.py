"""The three training-time forward paths for an approximate projection.

* MODEL mode    — bit-accurate emulated forward, proxy-activation backward
                  (paper Sec. 3.1): a ``jax.custom_vjp`` whose bwd is the
                  VJP of the smooth proxy forward.
* INJECT mode   — fast forward + calibrated error injection (Sec. 3.2).
* CALIBRATE     — runs both paths, returns the accurate value *and* a
                  freshly fitted calibration site (collected through scan).

All three dispatch through the backend registry: each takes an optional
``backend`` override (resolved per site by ``dense()``) so one model can
mix hardware targets.  The MODEL-mode ``custom_vjp`` wrapper is cached per
(backend, params, ablation-flag) instead of being rebuilt on every call —
per-projection rebuilds made every trace re-specialise an identical
closure.

**Approximate backward** (the training-side 18x lever): every wrapper
also has a *gated* variant taking an extra runtime ``gate`` primal (an
int32 scalar, sliced per site from ``ApproxCtx.bwd_gate``).  Its bwd is a
``lax.cond`` between the exact surrogate VJP (gate == 0) and the same VJP
evaluated at :func:`repro.core.proxy.int8_dequant`-quantized operands and
cotangent (gate > 0) — emulating dL/dx and dL/dW running on the cheap
int8 multiplier datapath instead of exact fp32 einsums.  Forward values
are bitwise unchanged either way, and because the gate is a jit
*argument*, flipping a site between exact and approximate backward never
retraces.  ``gate=None`` (the default everywhere) keeps the original
ungated wrappers byte-identical.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ApproxConfig, Backend
from repro.core import calibration, registry
from repro.hw import variation


def fast_forward(x, w, cfg: ApproxConfig, backend: Optional[Backend] = None):
    """The cheap forward whose residual the injection corrects.

    Type 1 (SC / approx-mult / log-mult): proxy-activation forward.
    Type 2 (analog): plain matmul (paper: 'normal Conv2d' on
    non-calibration batches; saturation only enters via fine-tuning).
    The choice is the spec's ``fast_forward`` handle (None => proxy).
    """
    backend = backend if backend is not None else cfg.backend
    spec = registry.get(backend)
    return spec.fast(x, w, cfg.params_for(backend))


def _gated_vjp(surrogate, x, w, g, gate):
    """(dL/dx, dL/dw) of one projection under the runtime backward gate.

    ``surrogate`` is the function whose VJP defines the backward (plain
    matmul, proxy forward, or proxy+epilogue).  ``gate`` is an int32
    scalar: 0 selects the exact surrogate VJP; >0 evaluates the same VJP
    at int8-quantized operands with an int8-quantized cotangent — the
    approximate-backward emulation (grad matmuls on the int8 datapath).
    ``gate=None`` short-circuits to the exact branch with no cond in the
    graph, keeping ungated callers byte-identical.  Only one branch of
    the ``lax.cond`` executes per step, and the gate is a jit argument —
    flipping it never recompiles.
    """
    from repro.core import proxy as proxy_lib  # deferred: no import cycle

    def exact_bwd(a, b, ct):
        _, vjp = jax.vjp(surrogate, a, b)
        return vjp(ct)

    def approx_bwd(a, b, ct):
        aq = proxy_lib.int8_dequant(a)             # per-row activation grid
        bq = proxy_lib.int8_dequant(b, axis=None)  # per-tensor weight grid
        ctq = proxy_lib.int8_dequant(ct)           # per-row cotangent grid
        _, vjp = jax.vjp(surrogate, aq, bq)
        return vjp(ctq)

    if gate is None:
        return exact_bwd(x, w, g)
    return jax.lax.cond(gate > 0, approx_bwd, exact_bwd, x, w, g)


# (spec-name, params, ablation-flag, gated) -> (spec, custom_vjp fn).  The
# cached spec is identity-checked on lookup so registry.register(...,
# override=True) — the documented spec-replacement escape hatch —
# invalidates stale wrappers instead of silently serving the old emulator
# in MODEL mode.
_MODEL_MODE_CACHE: dict = {}


def _model_mode_fn(backend, params, proxy_in_backward: bool, gated: bool = False):
    """Build (once per backend-spec/params/ablation/gated tuple) the
    MODEL-mode accurate-forward / proxy-backward ``custom_vjp`` projection.
    The gated variant takes an extra ``gate`` primal (None cotangent, like
    the rng key) selecting exact vs int8 backward at runtime."""
    spec = registry.get(backend)
    key = (spec.name, params, proxy_in_backward, gated)
    cached = _MODEL_MODE_CACHE.get(key)
    if cached is not None and cached[0] is spec:
        return cached[1]

    if proxy_in_backward:
        # Backward through the smooth proxy (Tab. 3) evaluated at the
        # same operands — the paper's approximation-proxy activation.
        surrogate = lambda a, b: spec.proxy_forward(a, b, params)
    else:
        # Tab. 2 ablation: pretend the accumulator were linear
        surrogate = lambda a, b: a @ b

    if gated:

        @jax.custom_vjp
        def f(x, w, key, gate):
            return spec.emulate(x, w, params, key)

        def fwd(x, w, key, gate):
            return f(x, w, key, gate), (x, w, gate)

        def bwd(res, g):
            x, w, gate = res
            gx, gw = _gated_vjp(surrogate, x, w, g, gate)
            return gx, gw, None, None

    else:

        @jax.custom_vjp
        def f(x, w, key):
            return spec.emulate(x, w, params, key)

        def fwd(x, w, key):
            return f(x, w, key), (x, w)

        def bwd(res, g):
            x, w = res
            gx, gw = _gated_vjp(surrogate, x, w, g, None)
            return gx, gw, None

    f.defvjp(fwd, bwd)
    _MODEL_MODE_CACHE[key] = (spec, f)
    return f


def model_mode_matmul(
    x, w, cfg: ApproxConfig, rng, backend: Optional[Backend] = None, gate=None
):
    """Accurate-forward / proxy-backward projection (MODEL mode).

    The rng key is an explicit custom_vjp primal (float0 cotangent): a
    closed-over traced key would leak across jax.checkpoint re-traces.
    ``gate`` (runtime int32 scalar) selects exact vs int8-approximate
    backward — see :func:`_gated_vjp`; the same precedent makes it a
    primal with a ``None`` cotangent.
    """
    backend = backend if backend is not None else cfg.backend
    params = cfg.params_for(backend)
    if gate is None:
        return _model_mode_fn(backend, params, cfg.proxy_in_backward)(x, w, rng)
    f = _model_mode_fn(backend, params, cfg.proxy_in_backward, gated=True)
    return f(x, w, rng, gate)


# (spec-name, params, ablation-flag, epi-structure, gated) -> (spec,
# custom_vjp fn).  The epilogue structure (which operands are present) is
# part of the key: a chip-aware correcting projection and a bare one trace
# different kernels.
_FUSED_MODE_CACHE: dict = {}


def _fused_mode_fn(backend, params, proxy_in_backward: bool, epi_struct,
                   gated: bool = False):
    """Build (and cache) the fused MODEL-mode projection: fused
    emulate+epilogue forward, proxy backward.

    The backward differentiates the *composed* surrogate — proxy forward
    followed by the same epilogue in jnp — so gradients see the chip gain
    and correction slope exactly as the unfused path's chain rule would.
    The gated variant threads the runtime int8-backward gate through the
    same surrogate (:func:`_gated_vjp`).
    """
    from repro.kernels.epilogue import apply_epilogue

    spec = registry.get(backend)
    key = (spec.name, params, proxy_in_backward, epi_struct, gated)
    cached = _FUSED_MODE_CACHE.get(key)
    if cached is not None and cached[0] is spec:
        return cached[1]

    def make_surrogate(epi):
        def surrogate(a, b):
            if not proxy_in_backward:
                y = a @ b
            else:
                y = spec.proxy_forward(a, b, params)
            return apply_epilogue(y, **epi)

        return surrogate

    if gated:

        @jax.custom_vjp
        def f(x, w, key, epi, gate):
            return spec.fused_emulate(x, w, params, key, epi)

        def fwd(x, w, key, epi, gate):
            return f(x, w, key, epi, gate), (x, w, epi, gate)

        def bwd(res, g):
            x, w, epi, gate = res
            gx, gw = _gated_vjp(make_surrogate(epi), x, w, g, gate)
            g_epi = jax.tree_util.tree_map(jnp.zeros_like, epi)
            return gx, gw, None, g_epi, None

    else:

        @jax.custom_vjp
        def f(x, w, key, epi):
            return spec.fused_emulate(x, w, params, key, epi)

        def fwd(x, w, key, epi):
            return f(x, w, key, epi), (x, w, epi)

        def bwd(res, g):
            x, w, epi = res
            gx, gw = _gated_vjp(make_surrogate(epi), x, w, g, None)
            g_epi = jax.tree_util.tree_map(jnp.zeros_like, epi)
            return gx, gw, None, g_epi

    f.defvjp(fwd, bwd)
    _FUSED_MODE_CACHE[key] = (spec, f)
    return f


def fused_model_mode_matmul(
    x, w, cfg: ApproxConfig, rng, epi: dict, backend: Optional[Backend] = None,
    gate=None,
):
    """Fused MODEL-mode projection: one kernel pass computes the emulated
    matmul, then the chip gain/offset and calibration correction (``epi``
    — see :func:`repro.kernels.epilogue.apply_epilogue`) apply to its
    output.  Requires the
    backend's spec to provide ``fused_emulate``; callers (``dense()``)
    fall back to the composed path when it doesn't.  ``gate`` routes the
    backward through the int8 emulation (see :func:`_gated_vjp`).
    """
    backend = backend if backend is not None else cfg.backend
    epi_struct = tuple(sorted(k for k, v in epi.items() if v is not None))
    epi = {k: v for k, v in epi.items() if v is not None}
    f = _fused_mode_fn(
        backend, cfg.params_for(backend), cfg.proxy_in_backward, epi_struct,
        gated=gate is not None,
    )
    if gate is None:
        return f(x, w, rng, epi)
    return f(x, w, rng, epi, gate)


# (kind, spec-name, params) -> (spec, custom_vjp fn): gated wrappers whose
# *forward* is an ordinary differentiable function (exact matmul / proxy /
# fast forward) — only the backward changes under the gate, so the
# ungated call sites keep their plain-autodiff graphs untouched.
_GATED_FWD_CACHE: dict = {}


def _gated_forward_fn(kind: str, backend, params):
    if kind == "exact":
        spec = None
        fwd_fn = lambda a, b: a @ b
        key = ("exact", None, None)
    else:
        spec = registry.get(backend)
        if kind == "fast":
            fwd_fn = lambda a, b: spec.fast(a, b, params)
        elif kind == "proxy":
            fwd_fn = lambda a, b: spec.proxy_forward(a, b, params)
        else:
            raise ValueError(f"unknown gated-forward kind {kind!r}")
        key = (kind, spec.name, params)
    cached = _GATED_FWD_CACHE.get(key)
    if cached is not None and (spec is None or cached[0] is spec):
        return cached[1]

    @jax.custom_vjp
    def f(x, w, gate):
        return fwd_fn(x, w)

    def fwd(x, w, gate):
        return f(x, w, gate), (x, w, gate)

    def bwd(res, g):
        x, w, gate = res
        gx, gw = _gated_vjp(fwd_fn, x, w, g, gate)
        return gx, gw, None

    f.defvjp(fwd, bwd)
    _GATED_FWD_CACHE[key] = (spec, f)
    return f


def gated_exact_matmul(x, w, gate):
    """Exact forward ``x @ w`` whose backward obeys the runtime int8 gate.

    This is where most of the training-side win lives: sites whose
    *forward* stays exact (warmup phases, skip-flagged or exact-mapped
    sites) can still push their two gradient matmuls — ~2/3 of training
    compute — onto the approximate int8 datapath.  With gate == 0 the
    VJP is the exact matmul VJP, bitwise identical to plain autodiff.
    """
    return _gated_forward_fn("exact", None, None)(x, w, gate)


def inject_mode_matmul(
    x, w, cfg: ApproxConfig, site, rng, backend: Optional[Backend] = None,
    gate=None,
):
    """Fast forward + injected calibrated error (INJECT mode)."""
    if gate is None:
        y = fast_forward(x, w, cfg, backend)
    else:
        b = backend if backend is not None else cfg.backend
        y = _gated_forward_fn("fast", b, cfg.params_for(b))(x, w, gate)
    if site is None:
        return y
    err = calibration.sample_error(site, y, rng, cfg.inject_std_scale)
    # The injected error perturbs values but should not steer gradients.
    return y + jax.lax.stop_gradient(err)


def proxy_only_matmul(x, w, cfg: ApproxConfig, backend: Optional[Backend] = None,
                      gate=None):
    """Proxy activation forward+backward, no injection (ablation mode)."""
    backend = backend if backend is not None else cfg.backend
    if gate is not None:
        return _gated_forward_fn("proxy", backend, cfg.params_for(backend))(
            x, w, gate
        )
    spec = registry.get(backend)
    return spec.proxy_forward(x, w, cfg.params_for(backend))


def calibrate_matmul(
    x,
    w,
    cfg: ApproxConfig,
    rng,
    backend: Optional[Backend] = None,
    *,
    site: str = "",
    chip=None,
    exact_ref: bool = False,
):
    """One calibration pass for this projection (paper Sec. 3.2).

    Runs the bit-accurate emulation (its output is also *used* as the layer
    output, matching the paper's accurate calibration batches), measures
    the residual against the fast forward, and fits the error statistics
    at the degree the site's backend prescribes.

    ``chip`` (a :class:`repro.hw.variation.ChipProfile`) perturbs the
    emulated output the way that physical device instance would, so the
    fitted statistics describe *this chip*, not the nominal spec.

    ``exact_ref`` fits the residual against the exact matmul instead of
    the fast forward, *conditioned on the emulated output* — the
    serving-side correction form: ``y_obs - predict_mean(stats, y_obs)``
    de-biases the chip's observed output toward the exact value.  The
    fit degree is floored at 1 there (a drifted gain is invisible to the
    Type-2 scalar stats).
    """
    backend = backend if backend is not None else cfg.backend
    spec = registry.get(backend)
    params = cfg.params_for(backend)
    y_acc = spec.emulate(x, w, params, rng)
    name = backend.value if isinstance(backend, Backend) else str(backend)
    y_acc = variation.apply_chip(y_acc, site, name, chip)
    degree = calibration.effective_degree(cfg, backend)
    if exact_ref:
        ref = (x @ w).astype(jnp.float32)
        resid = y_acc.astype(jnp.float32) - ref
        fitted = calibration.fit_error_stats(y_acc, resid, max(degree, 1))
    else:
        y_fast = spec.fast(x, w, params)
        resid = (y_acc - y_fast).astype(jnp.float32)
        fitted = calibration.fit_error_stats(y_fast, resid, degree)
    return y_acc, fitted
