"""Continuous-batching serving engine with per-request approximate-hardware
emulation.

The engine serves a queue of generation requests through fixed-shape
compiled steps — the serving-side counterpart of the training pipeline's
zero-retrace discipline:

* **Slots.**  Each distinct per-request serving config (an
  :class:`~repro.configs.base.ApproxConfig` resolved from the request's
  backend / site-override spec) owns a *lane*: one decode cache whose
  batch dimension is ``n_slots`` fixed slots.  Requests are admitted into
  free slots and evicted on completion via the
  :mod:`repro.models.decode` slot ops — pure ``dynamic_update_slice``
  writes, so churn never changes a compiled shape.
* **Bulk prefill.**  A prompt is prefilled in one full-sequence forward
  (:func:`repro.models.decode.prefill`), right-padded to a power-of-two
  bucket so arbitrary prompt lengths hit a bounded set of compiled
  graphs; the resulting cache slice is slot-inserted in the same jitted
  call.
* **Compiled-step cache.**  All jitted steps live in a
  :class:`~repro.training.steps.CompiledFnCache` (the PR-2 StepCache
  core) keyed on ``(kind, slot/bucket shape, ApproxConfig)``; its trace
  counters let tests assert zero retracing across a churning workload.
* **Per-request backends.**  A request naming an approximate backend is
  served with bit-accurate MODEL-mode emulation through the backend
  registry — the logits the deployed hardware would produce — while
  exact requests share the engine with it.  The multiplier-error
  emulators (approx-mult / log-mult) quantize with per-token activation
  scales (:func:`repro.core.proxy.row_scale`), so those requests' logits
  are independent of whatever shares their batch: a mixed-backend slot
  batch reproduces each request's solo oracle exactly.  (SC/analog keep
  per-tensor scales — their value->hardware mapping is a fixed device
  property — so their emulated logits are exact only at batch 1; MoE
  expert capacity likewise couples slot rows under capacity pressure.)
* **Chip fleets, drift, online recalibration** (``fleet=``).  With a
  :class:`repro.hw.Fleet`, each emulated lane is bound to one sampled
  device instance (a :class:`~repro.hw.variation.ChipProfile`), so a
  mixed queue fans out over *physical chips*, not just hardware kinds.
  Chip profiles and per-lane calibration stats are jit *arguments* of
  the compiled steps — every chip of one backend hits the same compiled
  graph (zero retraces across a fleet).  A ``drift=``
  :class:`~repro.hw.DriftModel` advances each lane's chip as tokens are
  served; the per-lane adaptive
  :class:`~repro.core.schedule.CalibrationController` watches the
  drifting emulated probe loss and, when it moves, refits the
  exact-reference error polynomials (``calib_exact_ref``) that decode /
  prefill subtract from every projection (``ctx.correct``) — online
  recalibration that pulls a drifted chip back toward fresh-chip loss.

``run_static_baseline`` is the pre-engine static-batch driver (waves of
padded requests, token-by-token prefill) with its two timing bugs fixed
— compile time is excluded from the throughput timers and reported
separately, and the decode clock stops only after the full
``(logits, cache)`` output is ready.  ``benchmarks/bench_serve.py``
measures the engine against it.
"""
from __future__ import annotations

import dataclasses
import queue as _pyqueue
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    ApproxConfig,
    Backend,
    CalibPolicy,
    Phase,
    TrainMode,
)
from repro.core import switch as switch_lib
from repro.core.approx_linear import ApproxCtx
from repro.core.schedule import CalibrationController, PhasePlan
from repro.hw import DriftModel, Fleet
from repro.hw import drift as drift_lib
from repro.models import decode as D
from repro.models.model import Model
from repro.training.losses import lm_loss
from repro.training.steps import CompiledFnCache


# ---------------------------------------------------------------------------
# Requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.

    ``backend`` names the approximate hardware this request's deployed
    model targets (a registry name; ``"exact"`` for the plain path), and
    ``site_backends`` optionally overrides backends per projection site
    (``(("attn_*", "sc"), ("mlp_*", "log_mult"))`` — AxTrain-style
    heterogeneous deployment).  With ``emulate=True`` (default) a
    non-exact request is served with bit-accurate MODEL-mode emulated
    logits; ``emulate=False`` serves it on the exact path (framework
    cost probing only).

    ``latency_tolerant`` marks traffic that accepts being parked on a
    degraded device: the fabric router preferentially places it on
    drifted chips awaiting recalibration (where quality traffic would
    first pay a synchronous refit), keeping those replicas earning while
    the recalibration service catches up.
    """

    rid: int
    prompt: Tuple[int, ...]
    max_new_tokens: int = 16
    backend: str = "exact"
    site_backends: Tuple[Tuple[str, str], ...] = ()
    emulate: bool = True
    temperature: float = 0.0
    latency_tolerant: bool = False

    def __post_init__(self):
        object.__setattr__(self, "prompt", tuple(int(t) for t in self.prompt))
        object.__setattr__(
            self,
            "site_backends",
            tuple((str(p), str(n)) for p, n in self.site_backends),
        )
        if not self.prompt:
            raise ValueError(f"request {self.rid}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"request {self.rid}: max_new_tokens must be >= 1")


def resolve_approx(req: Request, base: ApproxConfig) -> ApproxConfig:
    """The serving ApproxConfig a request runs under (its lane key).

    Hardware knobs (per-backend params) come from ``base``; the request
    only picks *which* backend(s) and whether to emulate.  Exact (or
    non-emulated) requests resolve to one shared inactive config so they
    all land in a single lane.
    """
    wants_approx = req.backend != Backend.EXACT.value or bool(req.site_backends)
    if not (wants_approx and req.emulate):
        return dataclasses.replace(
            base,
            backend=Backend.EXACT,
            mode=TrainMode.NO_MODEL,
            site_backends=(),
        )
    try:
        backend = Backend(req.backend)
    except ValueError:
        from repro.core import registry  # third-party name: must be registered

        registry.get(req.backend)  # raises KeyError listing what's available
        backend = req.backend
    return dataclasses.replace(
        base,
        backend=backend,
        mode=TrainMode.MODEL,
        site_backends=req.site_backends,
    )


def synthetic_requests(
    n: int,
    vocab_size: int,
    *,
    seed: int = 0,
    prompt_lens: Tuple[int, int] = (4, 16),
    gen_lens: Tuple[int, int] = (4, 16),
    backends: Sequence[str] = ("exact",),
    temperature: float = 0.0,
) -> List[Request]:
    """A mixed-length, mixed-backend request queue (drivers / benches)."""
    rnd = np.random.default_rng(seed)
    out = []
    for rid in range(n):
        P = int(rnd.integers(prompt_lens[0], prompt_lens[1] + 1))
        G = int(rnd.integers(gen_lens[0], gen_lens[1] + 1))
        prompt = tuple(int(t) for t in rnd.integers(0, vocab_size, size=P))
        out.append(
            Request(
                rid=rid,
                prompt=prompt,
                max_new_tokens=G,
                backend=backends[rid % len(backends)],
                temperature=temperature,
            )
        )
    return out


# ---------------------------------------------------------------------------
# Engine internals
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Active:
    """Per-slot state of an admitted request."""

    req: Request
    t_admit: float
    prefill_s: float = 0.0
    tokens: List[int] = dataclasses.field(default_factory=list)
    latencies: List[float] = dataclasses.field(default_factory=list)
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)


class _Lane:
    """All slots sharing one serving config (one compiled decode graph).

    With a fleet, a lane is additionally bound to one *device instance*:
    ``chip`` is its (drifting) ChipProfile, ``calib`` the per-chip
    exact-reference correction stats refreshed by online recalibration,
    and ``controller`` the adaptive cadence state machine.  Chip and
    calib are runtime arguments of the compiled steps — every lane of a
    backend shares one decode graph regardless of which chip it holds.
    """

    def __init__(
        self,
        approx: ApproxConfig,
        cache,
        n_slots: int,
        chip_id: int = -1,
        chip=None,
        switch: bool = False,
    ):
        self.approx = approx
        self.cache = cache
        self.slots: List[Optional[_Active]] = [None] * n_slots
        self.tokens = np.zeros((n_slots, 1), np.int32)
        self.pos = np.zeros((n_slots,), np.int32)
        # one-compile dispatch: per-slot backend switch indices (idle
        # slots sit at all-exact); the lane's approx is then the
        # *canonical* config and requests with different site maps share
        # this lane — the index matrix is a decode-step argument
        self.switch = switch
        self.site_idx = (
            np.zeros((n_slots, len(switch_lib.SITE_ORDER)), np.int32)
            if switch else None
        )
        # --- device-instance state (fleet serving) ---------------------
        self.chip_id = chip_id
        self.chip = chip
        self.calib = None
        self.controller: Optional[CalibrationController] = None
        self.tick = 0                   # engine steps seen (recal clock)
        self.recals = 0
        self.probe_losses: List[Tuple[int, float]] = []      # uncorrected
        self.corrected_losses: List[Tuple[int, float]] = []  # post-recal
        # external recalibration (serving fabric): True while a refit job
        # is outstanding at the recal service — the lane is "stale"
        self.awaiting_recal = False
        self.key: Optional[Tuple[ApproxConfig, int]] = None  # lanes dict key

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)


class Engine:
    """Continuous-batching serving engine over one model + params.

    ``submit`` enqueues requests; ``step`` runs one engine iteration
    (admissions, then one decode step per active lane); ``run`` drives
    the queue to completion and returns per-request results.  Completed
    requests stream through the optional ``stream`` callback as
    ``stream(rid, token, done)``.
    """

    def __init__(
        self,
        model: Model,
        params,
        *,
        n_slots: int = 4,
        max_seq: int = 128,
        approx_base: Optional[ApproxConfig] = None,
        min_bucket: int = 8,
        seed: int = 0,
        collect_logits: bool = False,
        stream: Optional[Callable[[int, int, bool], None]] = None,
        fleet: Optional[Fleet] = None,
        drift: Optional[DriftModel] = None,
        probe: Optional[Dict[str, Any]] = None,
        recalibrate_every: int = 8,
        recal_drift_threshold: float = 0.02,
        correct: bool = True,
        probe_corrected: bool = True,
        fused: Optional[bool] = None,
        switch: bool = False,
        warm_start: bool = False,
        external_recal: bool = False,
        on_recal_due: Optional[Callable[[Tuple[ApproxConfig, int], "_Lane"], None]] = None,
        fns: Optional[CompiledFnCache] = None,
        site_mask: Sequence[str] = (),
    ):
        """``fleet`` binds every emulated lane to a sampled device
        instance (one chip per lane, up to ``len(fleet)`` lanes per
        serving config); ``drift`` advances each lane's chip as tokens
        are served.  ``probe`` ({'tokens': [B,T], 'labels': [B,T]}) is
        the recalibration batch: its emulated loss is the drift signal
        the per-lane adaptive controller watches (base cadence
        ``recalibrate_every`` engine steps, halving when the loss moves
        by more than ``recal_drift_threshold`` relative), and each
        recalibration refits the lane's correction stats against the
        exact reference.  Without ``probe`` a synthetic random-token
        batch is generated — still a valid drift signal, just not a
        task-meaningful loss.

        ``correct=False`` serves chip lanes raw (no per-site mean-error
        subtraction) while still tracking drift and refitting stats.
        The correction targets the *exact* output — right for
        nominally-trained weights and for chips drifted outside the
        envelope variation-aware training absorbed; weights trained on
        the fleet's own variation may serve fresh chips better raw.

        ``probe_corrected=False`` skips the post-recalibration corrected
        probe eval (one extra forward per recalibration whose result
        only feeds ``fleet_report``) — the drift signal and stats refit
        are unaffected.

        ``fused`` routes decode through the fused hot path: epilogue-fused
        backend kernels (``ApproxCtx.fused``) plus the flash decode
        attention kernel (``serve_step(flash=...)``).  ``None`` defers to
        the ``REPRO_FUSED`` env toggle; chip profiles and calib stats are
        already jit arguments, so toggling lanes across chips never
        retraces.  Prefill and recalibration stay on the composed path
        (the bit-exactness oracle).

        ``switch`` turns on one-compile heterogeneous dispatch
        (:mod:`repro.core.switch`): every emulated request, whatever its
        backend / site-map, lands in ONE merged lane keyed on the
        canonical config, with a per-slot int32 index matrix as a decode
        argument — zero retraces under arbitrary heterogeneous traffic
        (one decode graph + one prefill graph per bucket, total).
        Per-slot selection computes each registered backend's branch and
        picks per row, so the merged lane trades per-token FLOPs
        (memory-bound decode absorbs it) for zero compiles.  Emulator
        batch-invariance caveats apply across a merged batch exactly as
        they do within any shared lane (per-tensor-scale sc/analog are
        solo-exact only at batch 1).  Incompatible with ``fleet`` (lanes
        would no longer map 1:1 onto chips) and MoE models (expert
        routing couples rows); exact/non-emulated requests keep their
        own static lane.

        ``warm_start`` seeds a newly bound chip's correction polynomials
        from the fleet's mean fitted stats (``Fleet.mean_calib``) instead
        of running the bind-time zero-stat recalibration fit — the first
        corrected probe then already beats the raw chip, and binding
        costs one cheap probe instead of a collect pass; the first
        *drift-triggered* recalibration still refits chip-specific
        stats.  Falls back to the bind-time fit while no chip in the
        fleet has been calibrated yet.

        ``external_recal`` hands drift-triggered recalibration to an
        off-hot-path service (the serving fabric's
        :class:`~repro.serving.recal.RecalService`): when a lane's
        adaptive controller says a refit is due, the engine calls
        ``on_recal_due(lane_key, lane)`` (marking the lane
        ``awaiting_recal``) instead of refitting inline, and refreshed
        coefficients arrive later through :meth:`push_calib` — applied at
        the next step boundary as a jit-argument pytree swap, so the hot
        path never blocks on a fit and coefficients never change
        mid-step.  Bind-time calibration still runs inline (it happens
        once, before the lane serves).

        ``fns`` shares a compiled-fn cache across engines: fabric
        replicas of one model compile each serving graph once, fleet-wide
        (chip profiles and calib stats are jit arguments already).

        ``site_mask`` (with ``switch=True``) demotes matching sites to
        exact on every admitted request — the per-chip stuck-at-fault
        demotion seam (:func:`repro.core.switch.mask_site_indices`);
        :meth:`demote_sites` swaps the mask at runtime with zero
        retraces."""
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.n_slots = int(n_slots)
        self.max_seq = int(max_seq)
        self.min_bucket = int(min_bucket)
        self.approx_base = approx_base if approx_base is not None else ApproxConfig()
        self.collect_logits = collect_logits
        self.stream = stream
        self.fleet = fleet
        self.drift = drift
        self.recalibrate_every = max(int(recalibrate_every), 1)
        self.recal_drift_threshold = float(recal_drift_threshold)
        self.correct = bool(correct)
        self.probe_corrected = bool(probe_corrected)
        if fused is None:
            from repro.kernels import ops as kops
            fused = kops.fused_default()
        self.fused = bool(fused)
        self.switch = bool(switch)
        self.warm_start = bool(warm_start)
        self.external_recal = bool(external_recal)
        self.on_recal_due = on_recal_due
        self.site_mask: Tuple[str, ...] = tuple(site_mask)
        self._push_q: _pyqueue.Queue = _pyqueue.Queue()
        self.recal_pushes = 0
        if self.switch and fleet is not None:
            raise ValueError(
                "Engine(switch=True) is incompatible with a fleet: merged "
                "heterogeneous lanes no longer map 1:1 onto chips "
                "(per-chip recalibration needs one config per lane)"
            )
        if self.switch and model.cfg.n_experts:
            raise ValueError(
                "Engine(switch=True) does not support MoE models: expert "
                "routing couples slot rows, so per-slot backend selection "
                "is ill-defined"
            )
        if probe is None and fleet is not None:
            rnd = np.random.default_rng(seed + 101)
            shape = (2, min(32, self.max_seq))
            probe = {
                "tokens": rnd.integers(0, self.cfg.vocab_size, shape, np.int32),
                "labels": rnd.integers(0, self.cfg.vocab_size, shape, np.int32),
            }
        self.probe = probe

        self.fns = fns if fns is not None else CompiledFnCache()
        # (serving config, lane index): with a fleet, one emulated config
        # spreads over several lanes — one per bound chip
        self.lanes: Dict[Tuple[ApproxConfig, int], _Lane] = {}
        self.pending: deque = deque()
        self.results: Dict[int, Dict[str, Any]] = {}

        self._rng = jax.random.PRNGKey(seed)
        self._sampler = np.random.default_rng(seed)
        self._tick = 0

        # accounting (steady-state timers exclude compile time)
        self.compile_s = 0.0
        self.compiles: List[Tuple[Tuple, float]] = []  # (graph key, seconds)
        self.prefill_s = 0.0
        self.decode_s = 0.0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.decode_steps = 0
        self.recalibrations = 0
        self._util: List[Tuple[int, int]] = []  # (active, capacity) per step

    # -- submission ------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt({len(req.prompt)}) + "
                f"gen({req.max_new_tokens}) exceeds max_seq={self.max_seq}"
            )
        # resolve once here (unknown backends fail at submit, not in the
        # loop); the queue carries (request, lane-key) pairs
        self.pending.append((req, resolve_approx(req, self.approx_base)))

    # -- compiled steps --------------------------------------------------
    def _call(self, key, fn, *args):
        """Invoke a compiled step; returns (out, seconds, compiled?).

        Blocks on the FULL output (cache included, not just logits)
        before stopping the clock, and flags calls that traced so compile
        time never pollutes steady-state throughput numbers.
        """
        before = self.fns.trace_counts.get(key, 0)
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        compiled = self.fns.trace_counts.get(key, 0) > before
        if compiled:
            self.compile_s += dt
            self.compiles.append((key, dt))
        return out, dt, compiled

    def _decode_key_fn(self, approx: ApproxConfig, chip_aware: bool = False):
        key = ("decode", self.n_slots, self.max_seq, approx,
               chip_aware and self.correct, chip_aware, self.fused)
        cfg, correct, fused = self.cfg, self.correct, self.fused

        def build():
            if chip_aware:
                # chip + per-chip correction stats are runtime arguments:
                # every chip of this serving config shares this graph
                def fn(params, cache, tokens, pos, rng, chip, calib):
                    ctx = ApproxCtx(cfg=approx, rng=rng, chip=chip,
                                    correct=correct, fused=fused)
                    return D.serve_step(
                        params, cache, tokens, pos, cfg, ctx=ctx, calib=calib,
                        flash=fused,
                    )

                return fn

            def fn(params, cache, tokens, pos, rng):
                ctx = (
                    ApproxCtx(cfg=approx, rng=rng, fused=fused)
                    if approx.active else None
                )
                return D.serve_step(
                    params, cache, tokens, pos, cfg, ctx=ctx, flash=fused
                )

            return fn

        return key, self.fns.get(key, build, donate_argnums=(1,))

    def _decode_switch_key_fn(self, approx: ApproxConfig):
        """Merged-lane decode: the per-slot backend index matrix is a
        runtime argument — ONE graph serves every heterogeneous mix."""
        key = ("decode_switch", self.n_slots, self.max_seq, approx,
               self.fused)
        cfg, fused = self.cfg, self.fused

        def build():
            def fn(params, cache, tokens, pos, rng, site_idx):
                ctx = ApproxCtx(cfg=approx, rng=rng, fused=fused,
                                site_idx=site_idx)
                return D.serve_step(
                    params, cache, tokens, pos, cfg, ctx=ctx, flash=fused
                )

            return fn

        return key, self.fns.get(key, build, donate_argnums=(1,))

    def _prefill_switch_key_fn(self, approx: ApproxConfig, bucket: int):
        """Switch-dispatched prefill: one graph per bucket for every
        site map (the request's [n_sites] index vector is an argument)."""
        key = ("prefill_switch", self.n_slots, self.max_seq, bucket, approx)
        cfg, S = self.cfg, self.max_seq

        def build():
            def fn(params, cache, tokens, length, slot, rng, site_idx):
                last, sub = D.prefill(
                    params, tokens, cfg,
                    lengths=length[None], max_seq=S, approx=approx, rng=rng,
                    backend_idx=site_idx,
                )
                return last[0], D.slot_insert(cfg, cache, sub, slot)

            return fn

        return key, self.fns.get(key, build, donate_argnums=(1,))

    def _prefill_key_fn(
        self, approx: ApproxConfig, bucket: int, chip_aware: bool = False
    ):
        # n_slots/max_seq key the donated cache operand's shape: engines
        # of different slot counts sharing one fabric-wide cache must not
        # collide on (and retrace) each other's prefill graphs
        key = ("prefill", self.n_slots, self.max_seq, bucket, approx,
               chip_aware and self.correct, chip_aware)
        cfg, S, correct = self.cfg, self.max_seq, self.correct

        def build():
            if chip_aware:
                def fn(params, cache, tokens, length, slot, rng, chip, calib):
                    last, sub = D.prefill(
                        params, tokens, cfg,
                        lengths=length[None], max_seq=S, approx=approx,
                        rng=rng, chip=chip, calib=calib, correct=correct,
                    )
                    return last[0], D.slot_insert(cfg, cache, sub, slot)

                return fn

            def fn(params, cache, tokens, length, slot, rng):
                last, sub = D.prefill(
                    params, tokens, cfg,
                    lengths=length[None], max_seq=S, approx=approx, rng=rng,
                )
                return last[0], D.slot_insert(cfg, cache, sub, slot)

            return fn

        return key, self.fns.get(key, build, donate_argnums=(1,))

    def _recalib_key_fn(self, approx: ApproxConfig):
        """Recalibration probe: one collect pass on this lane's chip.

        Returns ``(correction stats, uncorrected emulated probe loss)`` —
        the loss is the drift signal (chip moved => loss moved), the
        stats are the refreshed exact-reference error polynomials.
        """
        key = ("recalib", self.probe["tokens"].shape, approx)
        model = self.model

        def build():
            def fn(params, tokens, labels, rng, chip):
                out = model.apply(
                    params, {"tokens": tokens}, approx=approx, rng=rng,
                    collect=True, remat="none", chip=chip,
                    calib_exact_ref=True,
                )
                return out.collected, lm_loss(out.logits, labels)

            return fn

        return key, self.fns.get(key, build)

    def _probe_key_fn(self, approx: ApproxConfig):
        """Corrected-probe eval: the loss this lane actually serves at
        (chip perturbation + fitted correction applied)."""
        key = ("probe", self.probe["tokens"].shape, approx)
        model = self.model

        def build():
            def fn(params, tokens, labels, rng, chip, calib):
                out = model.apply(
                    params, {"tokens": tokens}, approx=approx, calib=calib,
                    rng=rng, remat="none", chip=chip, correct=True,
                )
                return lm_loss(out.logits, labels)

            return fn

        return key, self.fns.get(key, build)

    def _probe_raw_key_fn(self, approx: ApproxConfig):
        """Uncorrected emulated probe loss WITHOUT a stats refit — the
        warm-start drift-signal baseline (``_recalibrate`` measures the
        same loss as a side effect of its collect pass)."""
        key = ("probe_raw", self.probe["tokens"].shape, approx)
        model = self.model

        def build():
            def fn(params, tokens, labels, rng, chip):
                out = model.apply(
                    params, {"tokens": tokens}, approx=approx, rng=rng,
                    remat="none", chip=chip,
                )
                return lm_loss(out.logits, labels)

            return fn

        return key, self.fns.get(key, build)

    def _reset_key_fn(self):
        key = ("reset", self.n_slots, self.max_seq)
        cfg = self.cfg

        def build():
            return lambda cache, slot: D.slot_reset(cfg, cache, slot)

        return key, self.fns.get(key, build, donate_argnums=(0,))

    def _bucket(self, prompt_len: int) -> int:
        b = self.min_bucket
        while b < prompt_len:
            b *= 2
        return min(b, self.max_seq)

    def _next_rng(self):
        self._tick += 1
        return jax.random.fold_in(self._rng, self._tick)

    # -- scheduling ------------------------------------------------------
    def _lane_key(self, approx: ApproxConfig) -> ApproxConfig:
        """The config a request's lane is keyed on.  Under ``switch``,
        every emulated config collapses onto its canonical form — one
        merged lane for arbitrary heterogeneous maps; the map itself
        becomes the slot's runtime index row at admit time."""
        if self.switch and approx.active:
            return switch_lib.canonical(approx)
        return approx

    def _max_lanes(self, approx: ApproxConfig) -> int:
        """How many lanes this serving config may spread over: one chip
        each when a fleet serves it (retired chips excluded — fleet
        policy pulls them out of service), a single (nominal) lane
        otherwise."""
        if self.fleet is not None and approx.active:
            return len(self.fleet.active_ids())
        return 1

    def _new_lane(
        self, approx: ApproxConfig, index: int, switch: bool = False
    ) -> _Lane:
        cache = self.model.init_cache(self.n_slots, self.max_seq)
        chip = None
        chip_id = index
        if self.fleet is not None and approx.active:
            # bind the index-th ACTIVE chip: retired ids never serve again
            chip_id = self.fleet.active_ids()[index]
            chip = self.fleet.chip(chip_id)
        lane = _Lane(approx, cache, self.n_slots, chip_id=chip_id, chip=chip,
                     switch=switch)
        lane.key = (approx, index)
        self.lanes[(approx, index)] = lane
        if chip is not None:
            lane.controller = CalibrationController(
                PhasePlan((Phase(
                    TrainMode.MODEL,
                    steps=2**31 - 1,
                    calibrate=CalibPolicy.ADAPTIVE,
                    calibrate_every=self.recalibrate_every,
                    drift_threshold=self.recal_drift_threshold,
                ),)),
                approx,
            )
            warm = self.fleet.mean_calib() if self.warm_start else None
            if warm is not None:
                # warm start: seed the correction polynomials from the
                # fleet's mean fitted stats — no bind-time collect fit;
                # the raw probe is still measured as the drift baseline
                lane.calib = warm
                loss = self._probe_raw(lane)
                lane.probe_losses.append((lane.tick, loss))
                if self.probe_corrected:
                    lane.corrected_losses.append(
                        (lane.tick, self._probe_corrected_loss(lane))
                    )
            else:
                # bind-time recalibration: fit this chip's fresh
                # correction stats and record its fresh-chip probe loss
                # — the baseline online recalibration later recovers
                # toward
                loss = self._recalibrate(lane)
            lane.controller.begin_step(lane.tick)  # consume the "due now"
            lane.controller.record(lane.tick, loss)
        return lane

    def _lane_for(
        self, approx: ApproxConfig, switch: bool = False
    ) -> Optional[_Lane]:
        """A lane of this config with a free slot, growing the lane set
        chip by chip until the fleet is exhausted; None when saturated."""
        lanes = [l for (a, _), l in self.lanes.items() if a == approx]
        for lane in lanes:
            if lane.free_slots():
                return lane
        if len(lanes) < self._max_lanes(approx):
            return self._new_lane(approx, len(lanes), switch=switch)
        return lanes[0] if lanes else None

    # -- online recalibration -------------------------------------------
    def _recalibrate(self, lane: _Lane) -> float:
        """Refit the lane's correction stats on its (possibly drifted)
        chip; returns the uncorrected emulated probe loss (drift signal).
        """
        key, fn = self._recalib_key_fn(lane.approx)
        (calib, loss), _, _ = self._call(
            key, fn, self.params,
            jnp.asarray(self.probe["tokens"]), jnp.asarray(self.probe["labels"]),
            self._next_rng(), lane.chip,
        )
        lane.calib = calib
        # park the fitted stats in the fleet's per-chip store: the chip's
        # calibration state outlives this engine (Fleet.calib_for)
        if self.fleet is not None and 0 <= lane.chip_id < len(self.fleet):
            self.fleet.set_calib(lane.chip_id, calib)
        loss = float(loss)
        lane.recals += 1
        self.recalibrations += 1
        lane.probe_losses.append((lane.tick, loss))
        if self.probe_corrected:
            # the serving-quality signal (chip + correction), one extra
            # probe forward — disable for latency-sensitive deployments
            lane.corrected_losses.append(
                (lane.tick, self._probe_corrected_loss(lane))
            )
        return loss

    def force_recalibrate(self, lane: _Lane) -> float:
        """Synchronous refit on the serving path (the stale-chip stall):
        the fabric pays this before placing quality traffic on a lane
        whose drift signal fired but whose refreshed coefficients have
        not arrived yet.  Clears ``awaiting_recal`` and feeds the
        adaptive controller; returns the uncorrected probe loss."""
        loss = self._recalibrate(lane)
        lane.awaiting_recal = False
        if lane.controller is not None:
            lane.controller.record(lane.tick, loss)
        return loss

    def push_calib(
        self,
        lane_key: Tuple[ApproxConfig, int],
        calib,
        probe_loss: Optional[float] = None,
        corrected_loss: Optional[float] = None,
    ) -> None:
        """Deliver externally refitted correction coefficients (thread-
        safe).  The swap happens at the next step boundary
        (:meth:`apply_pushes` runs first thing in :meth:`step`), never
        mid-step — the recalibration service's hot-path contract."""
        self._push_q.put((lane_key, calib, probe_loss, corrected_loss))

    def apply_pushes(self) -> int:
        """Drain pending calibration pushes into their lanes — a pure
        jit-argument pytree swap per lane (the decode graph takes calib
        as a runtime operand), so applying a push never retraces."""
        applied = 0
        while True:
            try:
                lane_key, calib, raw, corrected = self._push_q.get_nowait()
            except _pyqueue.Empty:
                break
            lane = self.lanes.get(lane_key)
            if lane is None:
                continue  # lane evicted/retired while the fit ran
            lane.calib = calib
            lane.awaiting_recal = False
            lane.recals += 1
            self.recalibrations += 1
            self.recal_pushes += 1
            if raw is not None:
                lane.probe_losses.append((lane.tick, float(raw)))
                if lane.controller is not None:
                    lane.controller.record(lane.tick, float(raw))
            if corrected is not None:
                lane.corrected_losses.append((lane.tick, float(corrected)))
            if self.fleet is not None and 0 <= lane.chip_id < len(self.fleet):
                self.fleet.set_calib(lane.chip_id, calib)
            applied += 1
        return applied

    def _advance_chip(self, lane: _Lane, tokens: int) -> None:
        """Age the lane's chip by ``tokens`` served.  The authoritative
        age is the chip's FLEET-GLOBAL token counter: every lane bound to
        one chip credits the same counter and drifts its profile copy to
        the shared total (drift is a pure function of destination age),
        so two lanes on one chip always agree on its drift state."""
        if lane.chip is None or tokens <= 0:
            return
        if self.fleet is not None and 0 <= lane.chip_id < len(self.fleet):
            total = self.fleet.note_tokens(lane.chip_id, tokens)
            if self.drift is not None:
                delta = total - float(np.asarray(lane.chip["age"]))
                if delta > 0:
                    lane.chip = drift_lib.advance(lane.chip, delta, self.drift)
        elif self.drift is not None:
            lane.chip = drift_lib.advance(lane.chip, tokens, self.drift)

    def demote_sites(self, patterns: Sequence[str]) -> int:
        """Install a site demotion mask (``switch`` engines): matching
        sites decode exact (index 0) on every current AND future slot —
        the router's per-chip stuck-at-fault containment.  Pure runtime
        index-array swaps; returns how many lanes were rewritten."""
        self.site_mask = tuple(patterns)
        rewritten = 0
        for lane in self.lanes.values():
            if lane.switch and lane.site_idx is not None:
                lane.site_idx = switch_lib.mask_site_indices(
                    lane.site_idx, self.site_mask
                )
                rewritten += 1
        return rewritten

    def _probe_raw(self, lane: _Lane) -> float:
        key, fn = self._probe_raw_key_fn(lane.approx)
        loss, _, _ = self._call(
            key, fn, self.params,
            jnp.asarray(self.probe["tokens"]), jnp.asarray(self.probe["labels"]),
            self._next_rng(), lane.chip,
        )
        return float(loss)

    def _probe_corrected_loss(self, lane: _Lane) -> float:
        pkey, pfn = self._probe_key_fn(lane.approx)
        closs, _, _ = self._call(
            pkey, pfn, self.params,
            jnp.asarray(self.probe["tokens"]), jnp.asarray(self.probe["labels"]),
            self._next_rng(), lane.chip, lane.calib,
        )
        return float(closs)

    def _sample(self, req: Request, logits_row: np.ndarray) -> int:
        if req.temperature <= 0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / req.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self._sampler.choice(len(p), p=p))

    def _emit(self, st: _Active, slot_event: List[Dict[str, Any]], done: bool):
        tok = st.tokens[-1]
        slot_event.append({"rid": st.req.rid, "token": tok, "done": done})
        if self.stream is not None:
            self.stream(st.req.rid, tok, done)

    def _finish(self, lane: _Lane, slot: int) -> None:
        st = lane.slots[slot]
        self.results[st.req.rid] = {
            "tokens": list(st.tokens),
            "prefill_s": st.prefill_s,
            "latencies_s": list(st.latencies),
            "backend": st.req.backend,
            "emulated": lane.approx.active,
            "chip": lane.chip_id if lane.chip is not None else None,
            "logits": st.logits if self.collect_logits else None,
        }
        lane.slots[slot] = None
        # Evict: neutralize the freed slot (zero cache slice, token 0,
        # pos 0) so batch-coupled computations — MoE expert capacity,
        # the per-tensor activation scales of the sc/analog emulators —
        # see a canonical idle row, never a finished request's KV/state.
        # (Attention idle rows then stay canonical step to step; an SSM
        # idle row's state still evolves — boundedly, toward the token-0
        # fixed point — while it sits idle, one more reason per-tensor-
        # scale emulation is only exact at batch 1.)
        key, fn = self._reset_key_fn()
        out, _, _ = self._call(key, fn, lane.cache, jnp.int32(slot))
        lane.cache = out
        lane.tokens[slot, 0] = 0
        lane.pos[slot] = 0
        if lane.switch:
            lane.site_idx[slot] = 0  # idle rows decode exact

    def _admit(
        self, lane: _Lane, slot: int, req: Request,
        approx: Optional[ApproxConfig] = None,
    ) -> List[Dict[str, Any]]:
        P = len(req.prompt)
        L = self._bucket(P)
        toks = np.zeros((1, L), np.int32)
        toks[0, :P] = req.prompt
        chip_aware = lane.chip is not None
        idx_row = None
        if lane.switch:
            # the request's resolved map becomes this slot's index row;
            # prefill dispatches on it as a [n_sites] runtime vector
            idx_row = switch_lib.site_indices(
                approx if approx is not None else resolve_approx(req, self.approx_base)
            )
            if self.site_mask:
                # per-chip fault demotion: masked sites serve exact
                idx_row = switch_lib.mask_site_indices(idx_row, self.site_mask)
            key, fn = self._prefill_switch_key_fn(lane.approx, L)
            args = (
                self.params, lane.cache, jnp.asarray(toks),
                jnp.int32(P), jnp.int32(slot), self._next_rng(),
                jnp.asarray(idx_row),
            )
        else:
            key, fn = self._prefill_key_fn(lane.approx, L, chip_aware)
            args = (
                self.params, lane.cache, jnp.asarray(toks),
                jnp.int32(P), jnp.int32(slot), self._next_rng(),
            )
            if chip_aware:
                args += (lane.chip, lane.calib)
        (last, cache), dt, compiled = self._call(key, fn, *args)
        lane.cache = cache
        if chip_aware:
            self._advance_chip(lane, P)
        if not compiled:  # steady-state accounting: compiling calls are
            self.prefill_s += dt  # excluded from both time AND tokens
            self.prefill_tokens += P

        # per-request prefill_s is a steady-state number: a call that
        # traced reports its (much larger) duration under compile_s only
        st = _Active(
            req=req, t_admit=time.perf_counter(),
            prefill_s=0.0 if compiled else dt,
        )
        logits_row = np.asarray(last)
        if self.collect_logits:
            st.logits.append(logits_row)
        st.tokens.append(self._sample(req, logits_row))
        lane.slots[slot] = st
        lane.tokens[slot, 0] = st.tokens[-1]
        lane.pos[slot] = P
        if lane.switch:
            lane.site_idx[slot] = idx_row

        events: List[Dict[str, Any]] = []
        done = len(st.tokens) >= req.max_new_tokens
        self._emit(st, events, done)
        if done:
            self._finish(lane, slot)
        return events

    def _decode_lane(self, lane: _Lane) -> List[Dict[str, Any]]:
        chip_aware = lane.chip is not None
        if lane.switch:
            key, fn = self._decode_switch_key_fn(lane.approx)
            args = (
                self.params, lane.cache,
                jnp.asarray(lane.tokens), jnp.asarray(lane.pos),
                self._next_rng(), jnp.asarray(lane.site_idx),
            )
        else:
            key, fn = self._decode_key_fn(lane.approx, chip_aware)
            args = (
                self.params, lane.cache,
                jnp.asarray(lane.tokens), jnp.asarray(lane.pos),
                self._next_rng(),
            )
            if chip_aware:
                args += (lane.chip, lane.calib)
        (logits, cache), dt, compiled = self._call(key, fn, *args)
        lane.cache = cache
        if chip_aware:
            # the device ages by the tokens it actually produced
            self._advance_chip(lane, lane.n_active())
        logits_np = np.asarray(logits)

        events: List[Dict[str, Any]] = []
        n_active = 0
        for i, st in enumerate(lane.slots):
            if st is None:
                continue
            n_active += 1
            row = logits_np[i]
            if self.collect_logits:
                st.logits.append(row)
            st.tokens.append(self._sample(st.req, row))
            if not compiled:
                st.latencies.append(dt)
            lane.tokens[i, 0] = st.tokens[-1]
            lane.pos[i] += 1
            done = len(st.tokens) >= st.req.max_new_tokens
            self._emit(st, events, done)
            if done:
                self._finish(lane, i)
        self.decode_steps += 1
        if not compiled:  # steady-state accounting (see _admit)
            self.decode_s += dt
            self.decode_tokens += n_active
        return events

    # -- the engine loop -------------------------------------------------
    def step(self) -> List[Dict[str, Any]]:
        """One engine iteration: admit what fits, then decode every lane
        (running each chip-bound lane's recalibration first when its
        adaptive controller says the cadence is due — or, under
        ``external_recal``, flagging the lane and notifying the
        recalibration service instead).  Externally pushed coefficients
        are applied first, at this step boundary, never mid-step."""
        events: List[Dict[str, Any]] = []
        self.apply_pushes()
        deferred: deque = deque()
        while self.pending:
            req, approx = self.pending.popleft()
            lane = self._lane_for(
                self._lane_key(approx), switch=self.switch and approx.active
            )
            free = lane.free_slots() if lane is not None else []
            if free:
                events += self._admit(lane, free[0], req, approx)
            else:
                deferred.append((req, approx))
        self.pending = deferred

        active = sum(l.n_active() for l in self.lanes.values())
        capacity = max(1, self.n_slots * len(self.lanes))
        if active:
            self._util.append((active, capacity))
        for lane in list(self.lanes.values()):
            if lane.controller is not None and lane.n_active():
                lane.tick += 1
                if lane.controller.begin_step(lane.tick):
                    # drift detection in the loop: the controller halves
                    # its interval when the probe loss moves (the chip is
                    # drifting), backs off while it holds steady
                    if self.external_recal:
                        # off-hot-path recalibration: flag the lane stale
                        # and hand the refit to the service; coefficients
                        # come back through push_calib (one outstanding
                        # job per lane at a time)
                        if not lane.awaiting_recal:
                            lane.awaiting_recal = True
                            if self.on_recal_due is not None:
                                self.on_recal_due(lane.key, lane)
                    else:
                        lane.controller.record(
                            lane.tick, self._recalibrate(lane)
                        )
            if lane.n_active():
                events += self._decode_lane(lane)
        return events

    def run(self, requests: Optional[Sequence[Request]] = None) -> Dict[int, Dict]:
        """Drive the queue to completion; returns {rid: result}."""
        for r in requests or ():
            self.submit(r)
        while self.pending or any(l.n_active() for l in self.lanes.values()):
            self.step()
        return self.results

    # -- reporting -------------------------------------------------------
    @property
    def compile_stats(self) -> Dict[str, int]:
        return self.fns.stats()

    def metrics(self) -> Dict[str, Any]:
        lat = [
            t for r in self.results.values() for t in r["latencies_s"]
        ]
        util = (
            float(np.mean([a / c for a, c in self._util])) if self._util else 0.0
        )
        total_s = self.prefill_s + self.decode_s
        total_tok = self.prefill_tokens + self.decode_tokens
        return {
            "requests": len(self.results),
            "n_slots": self.n_slots,
            "lanes": len(self.lanes),
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "prefill_tok_s": self.prefill_tokens / max(self.prefill_s, 1e-9),
            "decode_tok_s": self.decode_tokens / max(self.decode_s, 1e-9),
            "total_tok_s": total_tok / max(total_s, 1e-9),
            "compile_s": self.compile_s,
            "fused": self.fused,
            "switch": self.switch,
            "p50_ms": float(np.percentile(lat, 50) * 1e3) if lat else 0.0,
            "p99_ms": float(np.percentile(lat, 99) * 1e3) if lat else 0.0,
            "slot_util": util,
            "recalibrations": self.recalibrations,
            "recal_pushes": self.recal_pushes,
            "site_mask": list(self.site_mask),
            "fleet_chips": len(self.fleet) if self.fleet is not None else 0,
            "compile_stats": self.compile_stats,
        }

    def fleet_report(self) -> List[Dict[str, Any]]:
        """Per chip-bound lane: drift/recalibration trajectory (the
        drift-recovery benchmark reads this).

        ``age_tokens`` is the chip's FLEET-GLOBAL token counter — how
        many tokens the chip served across every lane bound to it — not
        the lane-local count, so two lanes sharing one chip report the
        same drift age.  With a fleet, the report also carries the
        fleet's retirement ledger entries for chips this engine bound."""
        out = []
        for (_, idx), lane in sorted(self.lanes.items(), key=lambda kv: kv[0][1]):
            if lane.chip is None:
                continue
            if self.fleet is not None and 0 <= lane.chip_id < len(self.fleet):
                age = self.fleet.tokens_served(lane.chip_id)
                retired = self.fleet.is_retired(lane.chip_id)
            else:
                age = float(np.asarray(lane.chip["age"]))
                retired = False
            out.append({
                "chip": lane.chip_id,
                "backend": lane.approx.backend.value
                if isinstance(lane.approx.backend, Backend)
                else str(lane.approx.backend),
                "age_tokens": age,
                "recalibrations": lane.recals,
                "awaiting_recal": lane.awaiting_recal,
                "retired": retired,
                "probe_losses": [l for _, l in lane.probe_losses],
                "corrected_losses": [l for _, l in lane.corrected_losses],
            })
        return out


# ---------------------------------------------------------------------------
# Static-batch baseline (the pre-engine launch/serve.py driver, timing fixed)
# ---------------------------------------------------------------------------


def run_static_baseline(
    model: Model,
    params,
    requests: Sequence[Request],
    *,
    batch: int,
) -> Dict[str, Any]:
    """Serve ``requests`` the old static-batch way: waves of ``batch``
    requests, prompts padded to the wave max and streamed token-by-token
    through the decode path, then decode until the wave's longest request
    finishes (exact path only — the old driver never served emulation).

    Static-batching semantics caveat: a shorter prompt in a mixed-length
    wave is zero-padded to the wave max and its generation starts from
    the wave-max position with the pad tokens inside its causal context —
    its ``outputs`` entry is NOT the continuation of its own prompt
    alone.  That quality degradation (along with the padded wall-clock)
    is precisely the deficiency the slot engine removes; use the engine
    when per-request fidelity matters and this driver only as the
    throughput baseline.

    Timing fixes over the original driver: each wave's first (compiling)
    step runs on a scratch cache *outside* the throughput timers and is
    reported as ``compile_s``; the decode clock stops only after
    ``block_until_ready`` on the full ``(logits, cache)`` output.
    """
    cfg = model.cfg
    step = jax.jit(
        lambda p, c, t, pos: model.serve_step(p, c, t, pos),
        donate_argnums=(1,),
    )
    compile_s = prefill_s = decode_s = 0.0
    prefill_tokens = decode_tokens = 0
    compiled_shapes = set()
    outputs: Dict[int, List[int]] = {}

    for w0 in range(0, len(requests), batch):
        wave = list(requests[w0 : w0 + batch])
        B = len(wave)
        P = max(len(r.prompt) for r in wave)
        G = max(r.max_new_tokens for r in wave)
        S = P + G
        prompts = np.zeros((B, P), np.int32)
        for i, r in enumerate(wave):
            prompts[i, : len(r.prompt)] = r.prompt
        prompts = jnp.asarray(prompts)

        if (B, S) not in compiled_shapes:  # warm up outside the timers
            compiled_shapes.add((B, S))
            scratch = model.init_cache(B, S)
            t0 = time.perf_counter()
            out = step(params, scratch, prompts[:, :1], jnp.int32(0))
            jax.block_until_ready(out)
            compile_s += time.perf_counter() - t0

        cache = model.init_cache(B, S)
        t0 = time.perf_counter()
        logits = None
        for i in range(P):
            logits, cache = step(params, cache, prompts[:, i : i + 1], jnp.int32(i))
        jax.block_until_ready((logits, cache))
        prefill_s += time.perf_counter() - t0
        # tok/s counts USEFUL tokens (per-request true lengths), matching
        # the engine's accounting: the pad rows/steps the static driver
        # burns wall-clock on are precisely its inefficiency
        prefill_tokens += sum(len(r.prompt) for r in wave)

        wave_tokens: List[np.ndarray] = []
        t0 = time.perf_counter()
        cur = jnp.argmax(logits, -1)[:, None]
        for g in range(G):
            wave_tokens.append(np.asarray(cur[:, 0]))
            if g == G - 1:
                break
            logits, cache = step(params, cache, cur, jnp.int32(P + g))
            cur = jnp.argmax(logits, -1)[:, None]
        jax.block_until_ready((logits, cache))
        decode_s += time.perf_counter() - t0
        # G-1 decode steps run (the wave's first token comes from the
        # prefill logits, mirroring the engine's accounting): credit only
        # useful tokens actually produced by timed decode steps
        decode_tokens += sum(r.max_new_tokens - 1 for r in wave)

        stacked = np.stack(wave_tokens, axis=1)  # [B, G]
        for i, r in enumerate(wave):
            outputs[r.rid] = [int(t) for t in stacked[i, : r.max_new_tokens]]

    total_s = prefill_s + decode_s
    total_tok = prefill_tokens + decode_tokens
    return {
        "requests": len(requests),
        "batch": batch,
        "prefill_tokens": prefill_tokens,
        "decode_tokens": decode_tokens,
        "prefill_s": prefill_s,
        "decode_s": decode_s,
        "compile_s": compile_s,
        "prefill_tok_s": prefill_tokens / max(prefill_s, 1e-9),
        "decode_tok_s": decode_tokens / max(decode_s, 1e-9),
        "total_tok_s": total_tok / max(total_s, 1e-9),
        "outputs": outputs,
    }
