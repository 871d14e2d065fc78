"""``dense`` — the drop-in projection primitive for the whole model zoo.

Every matmul-shaped computation in every architecture (QKV/O, MLP, expert
FFNs, LM head, SSM in/out projections) routes through :func:`dense`, which
dispatches on the :class:`ApproxCtx` it is handed:

* no ctx / inactive config  -> plain ``x @ w`` (exact baseline)
* ``TrainMode.MODEL``       -> bit-accurate fwd, proxy bwd
* ``TrainMode.INJECT``      -> fast fwd + calibrated error injection
* ``TrainMode.PROXY_ONLY``  -> proxy activation only (ablation)
* ``ctx.collect=True``      -> calibration pass (accurate fwd + fit stats)

Which *hardware backend* a projection runs on is resolved per call site:
``cfg.backend_for(site)`` consults the config's ``site_backends`` override
map (first fnmatch pattern wins) before falling back to the default
backend, and the resolved backend flows into the registry-dispatched
injection/proxy/emulation paths.  One model can therefore mix targets —
e.g. SC attention projections with approx-mult FFNs.

The ctx also carries the per-layer calibration sites (sliced out of the
scan-stacked calibration pytree by the model) and a per-layer rng that is
folded per call-site name so two projections in one layer never share
noise streams.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ApproxConfig, Backend, TrainMode
from repro.core import calibration, injection, registry
from repro.core import switch as switch_lib
from repro.hw import variation


@dataclasses.dataclass
class ApproxCtx:
    """Per-layer context threaded through a model's apply function.

    ``blend`` is the sensitivity-profiling hook (repro.search.sensitivity):
    when set (a traced scalar), every non-exact projection returns
    ``y_exact + blend * (y_approx - y_exact)`` instead of ``y_approx``, so
    ``d loss / d blend`` at ``blend = 0`` is the first-order loss
    sensitivity of the approximation — grad(.)·Δ with the gradient flowing
    through the backend's proxy backward (MODEL mode).  ``None`` (the
    default) leaves every path byte-identical to before.

    ``chip`` is the device-instance hook (repro.hw): a ChipProfile pytree
    of runtime arrays describing one physical chip.  Every emulated
    forward (MODEL mode, calibration passes) is perturbed the way that
    instance would compute it — variation-aware training resamples the
    chip per step, the serving engine binds one per lane.  ``correct``
    additionally subtracts the fitted conditional-mean error
    (``calibration.predict_mean``) from MODEL-mode outputs using the
    ctx's calib stats — the serving-side online-recalibration
    correction; ``calib_exact_ref`` makes calibration passes fit those
    stats against the exact matmul (see ``injection.calibrate_matmul``).

    ``fused`` routes MODEL-mode projections through the backend's fused
    kernel (both unipolar planes, the rescale and the cast in one pass,
    then chip + correction on its output — the serving decode hot path)
    when the spec provides one; the composed sequence above is the
    bit-exactness oracle and the automatic fallback.

    ``site_idx`` is the one-compile heterogeneous-dispatch hook
    (:mod:`repro.core.switch`): an int32 index array over
    ``switch.SITE_ORDER`` selecting each site's backend from the
    registry-ordered switch table at *runtime*.  A ``[n_sites]`` vector
    dispatches via ``lax.switch`` (one branch executes — training /
    search / prefill); a ``[rows, n_sites]`` matrix (rows == the batch
    leading dim) dispatches per row via compute-all + ``lax.select_n``
    (the engine's merged heterogeneous serving lanes).  ``None`` (the
    default) keeps the static trace-time dispatch, which remains the
    bit-exactness oracle; calibration passes (``collect=True``) always
    use it — per-(site, backend) stat shapes cannot swap at runtime.

    ``bwd_gate`` is the approximate-*backward* hook
    (:mod:`repro.core.injection`): an int32 ``[n_sites]`` mask over
    ``switch.SITE_ORDER`` — 1 routes that site's two gradient matmuls
    (dL/dx, dL/dW) through the emulated int8 datapath, 0 keeps the exact
    VJP.  The mask is a runtime primal with a ``None`` cotangent, so
    flipping the gate (or the whole backward mode) mid-run never
    retraces; sensitivity profiling picks which sites stay exact
    (``search.sensitivity.backward_gate``).  Disabled during calibration
    passes and under the ``blend`` probe (both need the standard
    backward).  ``None`` (the default) leaves every VJP byte-identical
    to before.
    """

    cfg: ApproxConfig
    calib: Optional[Dict[str, Any]] = None  # site-name -> CalibSite
    rng: Optional[jax.Array] = None
    collect: bool = False                   # calibration pass?
    collected: Dict[str, Any] = dataclasses.field(default_factory=dict)
    blend: Optional[jax.Array] = None       # sensitivity interpolation knob
    chip: Optional[Dict[str, Any]] = None   # device-instance profile
    correct: bool = False                   # apply fitted mean-error correction
    calib_exact_ref: bool = False           # fit correction stats vs exact
    fused: bool = False                     # fused MODEL-mode hot path
    site_idx: Optional[jax.Array] = None    # runtime backend switch indices
    bwd_gate: Optional[jax.Array] = None    # runtime int8-backward gate [S]

    def site_rng(self, site: str) -> jax.Array:
        key = self.rng if self.rng is not None else jax.random.PRNGKey(0)
        return jax.random.fold_in(key, zlib.crc32(site.encode()) & 0x7FFFFFFF)

    def site_gate(self, site: str):
        """This site's scalar backward gate, or None when gating is off.

        Calibration passes and blend probes keep the standard backward —
        calibration fits value statistics (no grads wanted) and the
        sensitivity probe's d/d(blend) must flow through the same proxy
        VJP the profile is defined on.
        """
        if self.bwd_gate is None or self.collect or self.blend is not None:
            return None
        pos = switch_lib.site_pos(site)
        if pos is None:
            return None
        return self.bwd_gate[pos]

    def for_layer(self, calib_layer, rng_layer) -> "ApproxCtx":
        return dataclasses.replace(
            self, calib=calib_layer, rng=rng_layer, collected={}
        )


def skipped_site(site: str, cfg: ApproxConfig) -> bool:
    """True when ``dense()`` keeps this site exact regardless of the
    backend map (the config's skip_* flags).  Public because the search
    cost model (repro.search.costmodel) must price sites exactly the way
    ``dense()`` executes them."""
    if cfg.skip_router and site.endswith("router"):
        return True
    if cfg.skip_lm_head and site.endswith("lm_head"):
        return True
    return False


_skipped = skipped_site  # internal alias (historical name)


def _approx_branch(x, w, site: str, backend, ctx: ApproxCtx, rng, gate=None):
    """The non-exact projection body for ONE backend under the ctx's mode.

    Shared verbatim by the static path and every runtime-switch branch
    (:func:`_switch_dense`), so switch-dispatched == static-dispatched
    traces the same jaxpr per backend — the bit-exactness contract
    tests/test_dispatch.py enforces.  ``backend`` may be an enum member
    or a registry-name string; never exact (the callers' exact branch is
    a plain matmul).  ``gate`` (a runtime scalar or None) routes the
    backward through the int8 datapath — forward values are unchanged.
    """
    compute_dtype = x.dtype
    cfg = ctx.cfg
    bname = backend.value if isinstance(backend, Backend) else str(backend)
    if cfg.mode == TrainMode.MODEL:
        spec = registry.get(backend)
        if ctx.fused and ctx.blend is None and spec.fused_emulate is not None:
            # fused hot path: one kernel pass for the matmul, then chip
            # + correction on its output.  Bit-identical to the composed
            # sequence below — enforced by tests/test_fused.py.
            colgain, coladd = variation.chip_epilogue(
                site, bname, ctx.chip, w.shape[-1], compute_dtype
            )
            stats = (ctx.calib or {}).get(site) if ctx.correct else None
            epi = {
                "colgain": colgain,
                "coladd": coladd,
                "mean_coeffs": stats["mean"] if stats is not None else None,
                "mean_scale": stats["scale"] if stats is not None else None,
            }
            y = injection.fused_model_mode_matmul(
                x, w, cfg, rng, epi, backend, gate=gate
            )
        else:
            y = injection.model_mode_matmul(x, w, cfg, rng, backend, gate=gate)
            # device-instance perturbation: what THIS chip computes
            y = variation.apply_chip(y, site, bname, ctx.chip)
            if ctx.correct:
                stats = (ctx.calib or {}).get(site)
                if stats is not None:
                    # online-recalibration de-bias (stats fitted with
                    # calib_exact_ref against the exact reference)
                    y = y - calibration.predict_mean(stats, y).astype(y.dtype)
    elif cfg.mode == TrainMode.INJECT:
        site_stats = (ctx.calib or {}).get(site)
        y = injection.inject_mode_matmul(
            x, w, cfg, site_stats, rng, backend, gate=gate
        )
    elif cfg.mode == TrainMode.PROXY_ONLY:
        y = injection.proxy_only_matmul(x, w, cfg, backend, gate=gate)
    else:  # NO_MODEL with an active backend: plain matmul
        y = x @ w if gate is None else injection.gated_exact_matmul(x, w, gate)
    if ctx.blend is not None:
        # sensitivity profiling (see ApproxCtx.blend): interpolate the
        # approximate path toward exact so d loss/d blend |_{blend=0}
        # is the first-order sensitivity of this site's approximation
        exact = x @ w
        y = exact + ctx.blend.astype(exact.dtype) * (y - exact)
    return y


def _switch_dense(x, w, *, site: str, ctx: ApproxCtx):
    """Runtime-dispatched projection: ``ctx.site_idx`` picks the backend.

    ``site_idx[..., pos(site)]`` indexes the registry-ordered switch
    table (:func:`repro.core.switch.table`).  A per-site scalar index
    lowers to ``lax.switch`` — only the selected branch executes, and
    swapping the index array never retraces (O(1) compiles across a
    whole candidate set).  A per-row index (``[rows, n_sites]``, rows ==
    x's leading dim) computes every branch on the full batch and selects
    per row via ``lax.select_n`` — the engine's merged heterogeneous
    lanes, zero retraces under arbitrary per-slot maps.  Every branch
    body is the SAME function the static path runs
    (:func:`_approx_branch`), keeping switch == static bitwise per
    backend.
    """
    pos = switch_lib.site_pos(site)
    idx = ctx.site_idx[..., pos]
    rng = ctx.site_rng(site)
    gate = ctx.site_gate(site)
    # a closed candidate set (ApproxConfig.switch_backends) builds
    # branches only for its own backends — smaller graph, cheaper XLA
    # compile; the index arrays must be resolved against the same table
    # (subtable() is idempotent: normalizes exact-first sorted order)
    if ctx.cfg.switch_backends:
        names = switch_lib.subtable(ctx.cfg.switch_backends)
    else:
        names = switch_lib.table()

    def exact_branch(xx, ww):
        if gate is None:
            return xx @ ww
        return injection.gated_exact_matmul(xx, ww, gate)

    def make(bname):
        return lambda xx, ww: _approx_branch(
            xx, ww, site, bname, ctx, rng, gate
        )

    branches = [exact_branch] + [make(n) for n in names[1:]]
    if idx.ndim == 0:
        return jax.lax.switch(idx, branches, x, w)
    ys = [fn(x, w) for fn in branches]
    which = jnp.clip(idx, 0, len(ys) - 1).astype(jnp.int32)
    which = which.reshape(which.shape + (1,) * (ys[0].ndim - which.ndim))
    return jax.lax.select_n(jnp.broadcast_to(which, ys[0].shape), *ys)


def dense(x, w, b=None, *, site: str = "", ctx: Optional[ApproxCtx] = None):
    """Projection ``x @ w (+ b)`` through the configured approximate path.

    x: [..., K]; w: [K, N]; b: [N] or None.
    """
    compute_dtype = x.dtype
    cfg = ctx.cfg if ctx is not None else None
    if (
        ctx is not None
        and ctx.site_idx is not None
        and not ctx.collect
        and cfg.mode != TrainMode.NO_MODEL
        and switch_lib.site_pos(site) is not None
    ):
        # one-compile heterogeneous dispatch: the backend is a runtime
        # index (skip flags were folded to exact at index-resolution
        # time — switch.site_indices); the static chain below stays the
        # bit-exactness oracle
        y = _switch_dense(x, w, site=site, ctx=ctx)
    elif ctx is None or not cfg.active:
        gate = ctx.site_gate(site) if ctx is not None else None
        y = x @ w if gate is None else injection.gated_exact_matmul(x, w, gate)
    else:
        backend = cfg.backend_for(site)
        if backend == Backend.EXACT or _skipped(site, cfg):
            gate = ctx.site_gate(site)
            # exact-forward sites still take the int8 backward when gated
            # open — most of the training-compute win lives here (warmup
            # phases run every forward exact).
            y = (
                x @ w if gate is None
                else injection.gated_exact_matmul(x, w, gate)
            )
            if ctx.collect:
                # A calibration pass must emit stats for EVERY site the
                # calibration pytree was initialized with — dropping the
                # exact/skipped ones would change the train-state structure
                # (breaking checkpoint restore and forcing step retraces).
                # Sites absent from the tree (e.g. the never-calibrated
                # moe_router) must stay absent, so carry-through is keyed on
                # membership.
                prev = (ctx.calib or {}).get(site)
                if prev is not None:
                    ctx.collected[site] = prev
        else:
            rng = ctx.site_rng(site)
            if ctx.collect:
                y, fitted = injection.calibrate_matmul(
                    x, w, cfg, rng, backend,
                    site=site, chip=ctx.chip, exact_ref=ctx.calib_exact_ref,
                )
                ctx.collected[site] = fitted
            else:
                y = _approx_branch(
                    x, w, site, backend, ctx, rng, ctx.site_gate(site)
                )
    y = y.astype(compute_dtype)
    if b is not None:
        y = y + b.astype(compute_dtype)
    return y


def init_calibration(site_names, cfg: ApproxConfig, n_layers: int = 0):
    """Zero-initialized calibration pytree for a model.

    Returns {site: CalibSite} with every leaf stacked over layers when
    ``n_layers > 0`` (matching the scan-over-layers parameter layout).
    Each site's stats take the degree of the backend that site resolves
    to — the pytree is keyed per (site, backend) under heterogeneous
    configs.
    """
    one = {name: calibration.init_site_for(cfg, name) for name in site_names}
    if not n_layers:
        return one
    return jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (n_layers,) + leaf.shape).copy(), one
    )
