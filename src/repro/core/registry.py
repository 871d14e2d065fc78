"""Pluggable approximate-backend registry.

Every approximate-hardware target is described by one :class:`BackendSpec`
— its params dataclass, bit-accurate emulator, smooth proxy activation,
cheap fast-forward, calibration degree, and kernel handles — registered in
a module-level registry keyed by the :class:`~repro.configs.base.Backend`
value.  ``backends.py`` / ``proxy.py`` / ``injection.py`` /
``calibration.py`` and the models' ``dense()`` primitive all dispatch
through :func:`get`, so adding a hardware target is one kernel + one spec
registration instead of editing an ``if cfg.backend ==`` chain in six
files (see README.md, "Adding a backend").

The built-in specs (exact, sc, analog, approx_mult, log_mult) are defined
and registered by :mod:`repro.core.backends`; :func:`get` imports it
lazily so lookup works regardless of import order and without a cycle
(``backends`` -> ``proxy`` -> ``registry``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import Backend

# Emulators / proxies are pure functions of (x, w, params[, rng]) where
# ``params`` is the backend's frozen params dataclass (hashable, so specs
# and param sets can key jit-level caches).
EmulateFn = Callable[..., jax.Array]        # (x, w, params, rng) -> y
ForwardFn = Callable[..., jax.Array]        # (x, w, params) -> y


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Everything the framework needs to train for one hardware target.

    * ``name``          — registry key; must equal a ``Backend`` value.
    * ``params_cls``    — frozen dataclass of the backend's hardware knobs.
    * ``emulate``       — bit-accurate forward ``(x, w, params, rng) -> y``
                          (the expensive path: MODEL mode, calibration
                          batches, hardware eval).
    * ``proxy_forward`` — smooth surrogate ``(x, w, params) -> y`` whose
                          VJP is the MODEL-mode backward pass (Sec. 3.1).
    * ``fast_forward``  — the cheap INJECT-mode forward whose residual the
                          calibrated injection corrects; ``None`` means
                          "same as proxy_forward" (Type-1 backends).
                          Type-2 backends (analog) use a plain matmul.
    * ``calib_degree``  — fixed polynomial degree for the error fit, or
                          ``None`` to use ``ApproxConfig.poly_degree``
                          (analog pins 0: the paper's Type-2 scalar stats).
    * ``kernels``       — named kernel handles (the ``repro.kernels.ops``
                          wrappers) for benchmarks / introspection.
    * ``energy``        — parametric deployment-energy model: a callable
                          ``(params) -> float`` returning the relative
                          energy of ONE MAC on this hardware, in units of
                          one exact digital MAC (paper Tab. 1's relative
                          op costs, scaled by the backend knobs — e.g. SC
                          cost grows with stream length, analog cost with
                          ADC resolution).  ``None`` means "price it like
                          exact hardware" (1.0) — conservative for
                          third-party specs that haven't provided one.
                          Consumed by :mod:`repro.search.costmodel`.
    * ``fused_emulate`` — optional fused MODEL-mode forward
                          ``(x, w, params, rng, epi) -> y`` that applies
                          the chip/calibration epilogue ``epi`` (see
                          :func:`repro.kernels.epilogue.apply_epilogue`)
                          to the output of one fused matmul kernel.
                          ``None`` means "no
                          fused path": ``dense()`` falls back to the
                          composed emulate -> apply_chip -> correct
                          sequence, so third-party backends keep working
                          unfused.
    """

    name: str
    params_cls: type
    emulate: EmulateFn
    proxy_forward: ForwardFn
    fast_forward: Optional[ForwardFn] = None
    calib_degree: Optional[int] = None
    kernels: Mapping[str, Callable] = dataclasses.field(default_factory=dict)
    energy: Optional[Callable[[Optional[object]], float]] = None
    fused_emulate: Optional[Callable] = None  # (x, w, params, rng, epi) -> y

    def fast(self, x, w, params) -> jax.Array:
        fn = self.fast_forward if self.fast_forward is not None else self.proxy_forward
        return fn(x, w, params)

    def mac_energy(self, params) -> float:
        """Relative energy per MAC on this hardware (exact MAC = 1.0)."""
        if self.energy is None:
            return 1.0
        e = float(self.energy(params))
        if not e > 0.0:
            raise ValueError(
                f"backend {self.name!r}: energy model returned {e}; per-MAC "
                "energy must be > 0 (zero-cost hardware breaks Pareto search)"
            )
        return e


_REGISTRY: Dict[str, BackendSpec] = {}


_loading_builtins = False


def _ensure_builtins():
    # Built-in specs live in repro.core.backends; importing it registers
    # them.  Lazy so registry itself stays import-light and cycle-free.
    # Keyed on the EXACT sentinel (not registry emptiness): a third-party
    # spec registered before any core import must not mask the built-ins.
    global _loading_builtins
    if _loading_builtins or Backend.EXACT.value in _REGISTRY:
        return
    _loading_builtins = True
    try:
        import repro.core.backends  # noqa: F401
    finally:
        _loading_builtins = False


def register(spec: BackendSpec, *, override: bool = False) -> BackendSpec:
    """Add a backend spec to the registry (returns it, decorator-style)."""
    _ensure_builtins()  # name collisions with built-ins must fail HERE
    if not isinstance(spec.name, str) or not spec.name:
        raise ValueError(f"BackendSpec.name must be a non-empty string: {spec.name!r}")
    if spec.name in _REGISTRY and not override:
        raise ValueError(
            f"backend {spec.name!r} already registered; pass override=True to replace"
        )
    _REGISTRY[spec.name] = spec
    return spec


def get(backend: Union[Backend, str]) -> BackendSpec:
    """Look up the spec for a backend (enum member or registry name)."""
    _ensure_builtins()
    name = backend.value if isinstance(backend, Backend) else str(backend)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no backend {name!r} registered; available: {names()}"
        ) from None


def names() -> Tuple[str, ...]:
    """All registered backend names (exact included)."""
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def approx_names() -> Tuple[str, ...]:
    """All registered *approximate* backend names (exact excluded)."""
    return tuple(n for n in names() if n != Backend.EXACT.value)


# ---------------------------------------------------------------------------
# Shared split-unipolar plumbing
#
# Signed operands on unipolar hardware split into positive/negative planes
# (DESIGN notes Sec. 6): z_pos = xp@wp + xn@wn and z_neg = xp@wn + xn@wp,
# with layer output act(z_pos) - act(z_neg).  Emulators realise this as
# ONE physical accumulation per polarity over the concatenated 2K unipolar
# ports; this helper owns that concatenate/reshape plumbing (previously
# duplicated between the SC and analog emulators).
# ---------------------------------------------------------------------------


def split_unipolar_contract(x_halves, w_halves, matmul: Callable) -> jax.Array:
    """Contract split-unipolar operand planes through a unipolar matmul.

    ``x_halves = (xp, xn)`` with shape [..., K] (both >= 0), ``w_halves =
    (wp, wn)`` with shape [K, N].  ``matmul(a, b)`` is the backend's
    unipolar 2-D contraction; it is called once per output polarity on the
    [batch, 2K] activation plane.  Returns ``pos - neg`` reshaped to
    [..., N] (value-domain rescale is the caller's job).
    """
    xp, xn = x_halves
    wp, wn = w_halves
    K = xp.shape[-1]
    xcat = jnp.concatenate([xp, xn], axis=-1).reshape(-1, 2 * K)
    w_pos = jnp.concatenate([wp, wn], axis=0)  # [2K, N]
    w_neg = jnp.concatenate([wn, wp], axis=0)
    r = matmul(xcat, w_pos) - matmul(xcat, w_neg)
    return r.reshape(xp.shape[:-1] + (wp.shape[-1],))
