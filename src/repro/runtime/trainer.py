"""Fault-tolerant training loop.

Responsibilities beyond calling the step functions:

* **Phase pipeline** (paper Sec. 3.2/3.3): drives the declarative
  :class:`~repro.core.schedule.PhasePlan` — per step it resolves the
  active :class:`Phase`, pulls the matching jitted step from the
  :class:`~repro.training.steps.StepCache` (keyed on mode + per-phase
  LR/microbatch overrides + site-backend spec, so arbitrary phase
  sequences never retrace mid-run), and lets the
  :class:`~repro.core.schedule.CalibrationController` decide when a
  calibration batch runs (fixed cadence or adaptive drift-triggered).
* **Checkpoint/restart**: async snapshots every N steps; on a step
  failure (device loss, preemption — simulated by a fault hook in tests)
  the loop restores the latest generation and *replays* from there.  Data
  is splittable-deterministic, so replayed batches are identical.  The
  calibration-controller state rides inside every checkpoint, so a
  restart mid-phase resumes with the adaptive cadence and calibration
  loss history intact.  The restart budget is windowed: a run of
  ``restart_reset_steps`` consecutive successful steps refunds it, so a
  long job survives many *recoverable* failures while a persistent
  failure still aborts promptly.
* **Straggler watchdog**: per-step wall-time EWMA; steps slower than
  ``straggler_factor``x the EWMA *of the preceding steps* are logged and
  counted — on a real multi-host deployment this signal feeds the
  work-stealing data pipeline (any host can regenerate any shard).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs.base import ApproxConfig, Phase, TrainConfig, TrainMode
from repro.core.schedule import CalibrationController, PhasePlan
from repro.data import SyntheticLM
from repro.hw import Fleet, VariationModel
from repro.models.model import Model
from repro.training.steps import StepCache, init_train_state


@dataclasses.dataclass
class TrainReport:
    losses: List[float]
    step_times: List[float]
    restarts: int
    straggler_steps: int
    calibrations: int
    # --- phase-pipeline accounting -----------------------------------
    calib_losses: List[Tuple[int, float]] = dataclasses.field(default_factory=list)
    mode_steps: Dict[str, int] = dataclasses.field(default_factory=dict)
    phase_steps: Dict[str, int] = dataclasses.field(default_factory=dict)
    compile_stats: Dict[str, int] = dataclasses.field(default_factory=dict)
    fleet_steps: int = 0  # steps trained against a sampled device instance
    # --- approximate-backward accounting ------------------------------
    backward_steps: Dict[str, int] = dataclasses.field(default_factory=dict)
    gate_refreshes: int = 0                 # sensitivity-gate derivations
    gate_events: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list
    )                                       # (step, open-site count)


class Trainer:
    def __init__(
        self,
        model: Model,
        approx: ApproxConfig,
        tcfg: TrainConfig,
        data: SyntheticLM,
        ckpt_dir: str,
        *,
        seed: int = 0,
        straggler_factor: float = 3.0,
        fault_hook: Optional[Callable[[int], None]] = None,
        log_every: int = 0,
        restart_budget: int = 10,
        restart_reset_steps: int = 50,
        variation: Optional[VariationModel] = None,
        fleet_seed: Optional[int] = None,
    ):
        self.model = model
        self.approx = approx
        self.tcfg = tcfg
        self.data = data
        self.ckpt = CheckpointManager(ckpt_dir, keep=tcfg.keep_checkpoints)
        self.seed = seed
        self.straggler_factor = straggler_factor
        self.fault_hook = fault_hook
        self.log_every = log_every
        self.restart_budget = restart_budget
        self.restart_reset_steps = restart_reset_steps

        self.plan = PhasePlan.from_configs(approx, tcfg)
        self.controller = CalibrationController(self.plan, approx)
        # every step's state argument is the loop's only reference to it
        self.steps = StepCache(model, approx, tcfg, donate_state=True)
        # variation-aware phases (Phase.fleet > 0): seeded device fleets,
        # built lazily per distinct size.  The fleet seed is decoupled
        # from the data/init seed so a chip resample sweep holds data
        # fixed; chips are resampled round-robin per step, so the weights
        # learn the *distribution* of devices, not one lucky instance.
        self.variation = variation if variation is not None else VariationModel()
        self.fleet_seed = fleet_seed if fleet_seed is not None else seed + 7919
        self._fleets: Dict[int, Fleet] = {}
        # approximate-backward gating: if ANY phase gates the backward,
        # EVERY train step is built bwd-aware — the gate is a runtime
        # operand, so exact phases pass a zeros mask through the same
        # compiled graph and flipping Phase(backward=...) never retraces.
        self._bwd_any = self.plan.any_gated_backward
        self._gates: Dict[int, Tuple[int, np.ndarray]] = {}  # phase -> (epoch, mask)
        self._gate_refreshes = 0
        self._gate_events: List[Tuple[int, int]] = []

    # ------------------------------------------------------------------
    def _state_like(self):
        return init_train_state(
            self.model, jax.random.PRNGKey(self.seed), self.approx,
            self.tcfg,
        )

    def init_or_restore(self):
        """Fresh train state, or the latest checkpoint (which also
        reloads the calibration-controller state saved alongside it)."""
        like = self._state_like()
        if self.ckpt.latest_step() is not None:
            try:
                full = self.ckpt.restore(
                    dict(like, sched=self.controller.to_tree())
                )
            except AssertionError:
                # pre-phase-pipeline checkpoint without a sched subtree:
                # restore the train state, start the controller fresh
                self.controller = CalibrationController(self.plan, self.approx)
                return self.ckpt.restore(like)
            self.controller.load_tree(full.pop("sched"))
            return full
        # no checkpoint: the controller must restart from scratch too —
        # a failure before the first save otherwise replays with the
        # aborted attempt's cadence/loss state and skips the phase-entry
        # calibration (stats would stay at their zero init)
        self.controller = CalibrationController(self.plan, self.approx)
        return like

    def _save(self, step: int, state):
        self.ckpt.save(step, dict(state, sched=self.controller.to_tree()))

    def _chip_for(self, phase: Phase, step: int):
        """The device instance this step trains against (None = nominal).

        Only modes whose compiled graph actually consumes the chip get
        one: MODEL/INJECT steps (emulated forward / chip-fitted injection
        stats) and any phase running calibration batches.  PROXY_ONLY and
        exact phases without calibration would train bit-identically to
        nominal while paying for a chip-aware graph — and misreport
        themselves as variation-aware.
        """
        if not phase.fleet or not self.approx.active:
            return None
        from repro.configs.base import CalibPolicy

        if (
            phase.mode in (TrainMode.NO_MODEL, TrainMode.PROXY_ONLY)
            and phase.calibrate == CalibPolicy.OFF
        ):
            return None
        fleet = self._fleets.get(phase.fleet)
        if fleet is None:
            fleet = self._fleets[phase.fleet] = Fleet(
                phase.fleet, seed=self.fleet_seed, variation=self.variation
            )
        return fleet.chip_for_step(step)

    def _step_fn(self, step: int, chip_aware: bool = False):
        """The jitted train step + label for a global step (cache-backed)."""
        index, phase, _ = self.plan.phase_at(step)
        fn = self.steps.train(
            phase.mode, lr_scale=phase.lr_scale,
            microbatches=phase.microbatches, chip_aware=chip_aware,
            bwd_aware=self._bwd_any,
        )
        label = phase.name if len(self.plan.phases) > 1 else phase.mode.value
        return fn, label, phase

    def _bwd_gate_for(self, index: int, phase: Phase, step: int,
                      sip: int, state, batch):
        """This step's approximate-backward gate mask (None = no gating).

        ``backward="exact"`` phases pass a zeros mask (the compiled step
        is shared, so the operand must still be threaded);
        ``backward="approx"`` derives the sensitivity gate once at phase
        entry; ``backward="auto"`` re-derives it every
        ``phase.gate_every`` steps.  Derivation runs through the run's
        own StepCache, so all refreshes share one compiled blend-grad
        graph — a gate refresh costs zero new traces after the first.
        """
        if not self._bwd_any:
            return None
        from repro.core import switch as switch_lib

        n_sites = len(switch_lib.SITE_ORDER)
        if phase.backward == "exact":
            return np.zeros(n_sites, np.int32)
        epoch = sip // phase.gate_every if phase.backward == "auto" else 0
        cached = self._gates.get(index)
        if cached is not None and cached[0] == epoch:
            return cached[1]
        from repro.search import sensitivity

        mask = sensitivity.backward_gate(
            self.model, state["params"], batch, self.approx,
            frac=phase.gate_frac, seed=self.seed, fns=self.steps,
        )
        self._gates[index] = (epoch, mask)
        self._gate_refreshes += 1
        self._gate_events.append((step, int(mask.sum())))
        return mask

    # ------------------------------------------------------------------
    def run(self, total_steps: Optional[int] = None) -> TrainReport:
        total = total_steps or self.plan.total_steps
        state = self.init_or_restore()
        start = int(state["step"])
        losses: List[float] = []
        times: List[float] = []
        calib_losses: List[Tuple[int, float]] = []
        mode_steps: Dict[str, int] = {}
        phase_steps: Dict[str, int] = {}
        backward_steps: Dict[str, int] = {}
        restarts = 0
        fleet_steps = 0
        window_restarts = 0    # failures since the last budget refund
        success_streak = 0     # counts NEW-progress steps only (see below)
        best_step = start      # high-water mark of completed steps
        stragglers = 0
        calibrations = 0
        ewma = None

        step = start
        while step < total:
            try:
                if self.fault_hook is not None:
                    self.fault_hook(step)
                rng = jax.random.fold_in(jax.random.PRNGKey(self.seed + 17), step)
                batch = self.data.batch_at(step)
                # variation-aware phase: this step's device instance (a
                # runtime pytree — switching chips never retraces)
                cur_index, cur_phase, cur_sip = self.plan.phase_at(step)
                chip = self._chip_for(cur_phase, step)
                chip_key = step % cur_phase.fleet if chip is not None else -1
                t0 = time.perf_counter()
                if self.controller.begin_step(step):
                    cal = self.steps.calibration(chip_aware=chip is not None)
                    state, cmetrics = (
                        cal(state, batch, rng, chip)
                        if chip is not None
                        else cal(state, batch, rng)
                    )
                    closs = float(cmetrics["loss"])
                    # keyed on the chip: the adaptive policy must compare
                    # same-chip losses (fleet spread is not drift)
                    self.controller.record(step, closs, key=chip_key)
                    calib_losses.append((step, closs))
                    calibrations += 1
                fn, label, phase = self._step_fn(step, chip_aware=chip is not None)
                # approximate-backward gate (runtime operand; None when no
                # phase in this plan gates the backward)
                gate = self._bwd_gate_for(
                    cur_index, cur_phase, step, cur_sip, state, batch
                )
                args = [state, batch, rng]
                if chip is not None:
                    fleet_steps += 1
                    args.append(chip)
                if gate is not None:
                    args.append(gate)
                state, metrics = fn(*args)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                if not np.isfinite(loss):
                    raise FloatingPointError(f"non-finite loss at step {step}")
                losses.append(loss)
                times.append(dt)
                # compare against the EWMA of *prior* steps: folding dt in
                # first inflates the threshold by ~10% and hides stragglers
                if ewma is not None and dt > self.straggler_factor * ewma and len(times) > 3:
                    stragglers += 1
                ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
                mode_steps[phase.mode.value] = mode_steps.get(phase.mode.value, 0) + 1
                phase_steps[label] = phase_steps.get(label, 0) + 1
                backward_steps[phase.backward] = (
                    backward_steps.get(phase.backward, 0) + 1
                )
                # only NEW progress counts toward the refund: replayed
                # steps always succeed (the failure hasn't recurred yet),
                # so counting them would let a persistent failure sitting
                # far past the last checkpoint retry forever
                if step + 1 > best_step:
                    best_step = step + 1
                    success_streak += 1
                if window_restarts and success_streak >= self.restart_reset_steps:
                    window_restarts = 0  # stable again: refund the budget
                if self.log_every and step % self.log_every == 0:
                    print(f"[{label}] step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)")
                if (step + 1) % self.tcfg.checkpoint_every == 0 or step + 1 == total:
                    self._save(step + 1, state)
                step += 1
            except (FloatingPointError, RuntimeError) as e:  # device loss etc.
                restarts += 1
                window_restarts += 1
                success_streak = 0
                if window_restarts > self.restart_budget:
                    raise
                print(f"[trainer] step {step} failed ({e}); restoring latest checkpoint")
                state = self.init_or_restore()
                step = int(state["step"])
        self.ckpt.wait()
        return TrainReport(
            losses,
            times,
            restarts,
            stragglers,
            calibrations,
            calib_losses=calib_losses,
            mode_steps=mode_steps,
            phase_steps=phase_steps,
            compile_stats=self.steps.stats(),
            fleet_steps=fleet_steps,
            backward_steps=backward_steps,
            gate_refreshes=self._gate_refreshes,
            gate_events=list(self._gate_events),
        )
