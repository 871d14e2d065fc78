"""Fault-tolerant trainer loop: restarts, phase pipeline, checkpoint
cadence, compiled-step cache (zero mid-run retracing)."""
import itertools

import jax
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import (
    AnalogParams,
    ApproxConfig,
    Backend,
    Phase,
    TrainConfig,
    TrainMode,
)
from repro.data import SyntheticLM
from repro.models import build_model
from repro.runtime.trainer import Trainer

pytestmark = pytest.mark.slow


def _mk(tmp_path, fault_hook=None, **kw):
    cfg = get_smoke_config("qwen2.5-3b")
    m = build_model(cfg)
    approx = ApproxConfig(
        backend=Backend.ANALOG, mode=TrainMode.INJECT,
        analog=AnalogParams(array_size=16), calibrate_every=4,
    )
    tkw = dict(
        total_steps=10, warmup_steps=1, inject_steps=7, finetune_steps=3,
        checkpoint_every=3, learning_rate=1e-3,
    )
    tkw.update({k: v for k, v in kw.items() if k in TrainConfig.__dataclass_fields__})
    trkw = {k: v for k, v in kw.items() if k not in TrainConfig.__dataclass_fields__}
    if tkw.get("phases"):
        tkw["inject_steps"] = tkw["finetune_steps"] = 0
    tcfg = TrainConfig(**tkw)
    data = SyntheticLM(cfg.vocab_size, 16, 4, seed=2)
    return Trainer(m, approx, tcfg, data, str(tmp_path), fault_hook=fault_hook, **trkw)


def test_full_phase_run(tmp_path):
    tr = _mk(tmp_path)
    rep = tr.run()
    assert len(rep.losses) == 10
    assert rep.restarts == 0
    # calibration at steps 0 and 4 (inject phase only)
    assert rep.calibrations == 2


def test_restart_resumes_from_checkpoint(tmp_path):
    fails = {"n": 0}

    def fault(step):
        if step == 5 and fails["n"] == 0:
            fails["n"] += 1
            raise RuntimeError("simulated preemption")

    tr = _mk(tmp_path, fault_hook=fault)
    rep = tr.run()
    assert rep.restarts == 1
    # steps 3 and 4 replayed after restore from the step-3 checkpoint
    assert len(rep.losses) == 12


def test_deterministic_replay(tmp_path):
    """Replayed steps see identical data (splittable determinism)."""
    rep_a = _mk(tmp_path / "a").run()

    fails = {"n": 0}

    def fault(step):
        if step == 4 and fails["n"] == 0:
            fails["n"] += 1
            raise RuntimeError("boom")

    rep_b = _mk(tmp_path / "b", fault_hook=fault).run()
    # final losses agree: the restarted run converges through the same data
    assert abs(rep_a.losses[-1] - rep_b.losses[-1]) < 1e-4


def test_too_many_restarts_raises(tmp_path):
    def always_fail(step):
        raise RuntimeError("persistent failure")

    tr = _mk(tmp_path, fault_hook=always_fail)
    with pytest.raises(RuntimeError):
        tr.run()


def test_restart_budget_refunds_after_stable_stretch(tmp_path):
    """Sporadic recoverable failures over a long job must not exhaust the
    budget: a stretch of successful steps resets the failure window."""
    failed = set()

    def fault(step):
        if step in (4, 10, 16) and step not in failed:
            failed.add(step)
            raise RuntimeError("sporadic preemption")

    tr = _mk(
        tmp_path, fault_hook=fault, total_steps=18, inject_steps=14,
        finetune_steps=4, restart_budget=2, restart_reset_steps=3,
    )
    rep = tr.run()
    # 3 lifetime restarts exceed the per-window budget of 2, but never
    # within one window — the run completes
    assert rep.restarts == 3
    assert len(rep.losses) > 18  # replayed steps


def test_persistent_failure_past_refund_window_still_aborts(tmp_path):
    """Replayed steps must not refund the budget: a deterministic failure
    sitting further than restart_reset_steps past the last checkpoint
    replays >= restart_reset_steps successes each cycle, and counting
    those would retry forever instead of aborting."""
    def fault(step):
        if step == 6:
            raise RuntimeError("deterministic failure")

    tr = _mk(
        tmp_path, fault_hook=fault, checkpoint_every=100,
        restart_budget=2, restart_reset_steps=2,
    )
    with pytest.raises(RuntimeError):
        tr.run()


def test_straggler_compares_against_prior_ewma(tmp_path, monkeypatch):
    """A step just above factor x the *prior* EWMA counts; folding the
    slow step into the EWMA first would inflate the threshold and miss it."""
    import repro.runtime.trainer as trainer_mod

    # dts: 1, 1, 1, 1, 3.5, 1 — with straggler_factor=3 the 3.5 step is
    # 3.5 > 3*1.0 vs the prior EWMA, but 3.5 < 3*1.25 after folding in
    dts = [1.0, 1.0, 1.0, 1.0, 3.5, 1.0]
    ticks = itertools.chain.from_iterable((sum(dts[:i]), sum(dts[:i]) + dts[i])
                                          for i in range(len(dts)))
    ticks = iter(list(ticks) + [999.0] * 8)
    monkeypatch.setattr(trainer_mod.time, "perf_counter", lambda: next(ticks))
    tr = _mk(tmp_path, total_steps=6, inject_steps=6, finetune_steps=0)
    rep = tr.run()
    assert rep.straggler_steps == 1


# ---------------------------------------------------------------------------
# Declarative multi-phase pipeline
# ---------------------------------------------------------------------------

INTERLEAVED = (
    Phase.exact(2, name="warmup"),
    Phase.inject(3),
    Phase.model(2),
    Phase.inject(3),          # revisits the inject graph — must not retrace
    Phase.proxy(2),
    Phase.model(2),           # revisits the model graph
    Phase(TrainMode.INJECT, 2, calibrate="off", lr_scale=0.5, name="anneal"),
)


def test_interleaved_phases_compile_each_step_exactly_once(tmp_path):
    """The retracing guard: across an interleaved multi-phase run every
    distinct compiled graph traces exactly once — revisited modes hit the
    StepCache, and a per-phase override (lr_scale) gets its own entry."""
    tr = _mk(tmp_path, phases=INTERLEAVED, total_steps=16)
    rep = tr.run()
    assert len(rep.losses) == 16
    # 5 distinct train graphs (no_model / inject / model / proxy_only /
    # inject@lr0.5) + 1 calibration graph
    assert rep.compile_stats == {"built": 6, "traces": 6, "retraces": 0}
    assert all(c == 1 for c in tr.steps.trace_counts.values())
    assert rep.mode_steps == {"no_model": 2, "inject": 8, "model": 4, "proxy_only": 2}
    assert rep.phase_steps["warmup"] == 2 and rep.phase_steps["anneal"] == 2
    # calibration ran at each every_n inject phase's entry only (cadence 4
    # exceeds the 3-step phases), never in warmup/model/proxy/off phases
    calib_steps = [s for s, _ in rep.calib_losses]
    assert calib_steps == [2, 7]
    assert rep.calibrations == len(rep.calib_losses) == 2


def test_calibration_loss_is_recorded(tmp_path):
    import numpy as np

    rep = _mk(tmp_path).run()
    assert rep.calibrations == 2
    assert [s for s, _ in rep.calib_losses] == [0, 4]
    assert all(np.isfinite(l) for _, l in rep.calib_losses)


def test_restart_mid_phase_resumes_phase_and_calibration_state(tmp_path):
    """Preemption inside phase 2 of 3 must resume in that phase with the
    adaptive calibration state intact: the restarted run's calibration
    decisions and losses replay identically to an uninterrupted run."""
    phases = (
        Phase.exact(4, name="warmup"),
        Phase.inject(8, calibrate="adaptive", name="inject"),
        Phase.model(4, name="finetune"),
    )
    rep_a = _mk(tmp_path / "a", phases=phases, total_steps=16).run()

    failed = {"n": 0}

    def fault(step):
        # mid inject phase, off the checkpoint cadence so steps replay
        if step == 10 and failed["n"] == 0:
            failed["n"] += 1
            raise RuntimeError("preempted mid-phase")

    tr_b = _mk(tmp_path / "b", phases=phases, total_steps=16, fault_hook=fault)
    rep_b = tr_b.run()
    assert rep_b.restarts == 1
    # resumed in the inject phase: extra (replayed) steps land there
    assert rep_b.phase_steps["warmup"] == rep_a.phase_steps["warmup"]
    assert rep_b.phase_steps["inject"] > rep_a.phase_steps["inject"]
    assert rep_b.phase_steps["finetune"] == rep_a.phase_steps["finetune"]
    # identical calibration decisions (adaptive controller state rode the
    # checkpoint; replayed calibration steps dedupe to the same set)
    calib_a = dict(rep_a.calib_losses)
    calib_b = dict(rep_b.calib_losses)
    assert set(calib_a) == set(calib_b)
    for s in calib_a:
        assert abs(calib_a[s] - calib_b[s]) < 1e-4
    # converges to the same trajectory
    assert abs(rep_a.losses[-1] - rep_b.losses[-1]) < 1e-4


def test_spans_count_steps_and_calibrations(tmp_path):
    tr = _mk(tmp_path, total_steps=6, inject_steps=6, finetune_steps=0)
    rep = tr.run()
    spans = tr.spans.snapshot()["spans"]
    assert spans["trainer.step"]["count"] == len(rep.losses) == 6
    for child in ("trainer.step.dispatch", "trainer.step.wait", "trainer.data"):
        assert spans[child]["count"] == 6
    assert spans["trainer.calibrate"]["count"] == rep.calibrations == 2
    assert spans["trainer.checkpoint"]["count"] == 2   # steps 3 and 6
    # a step's span is its dispatch and its loss sync: it leaves out the
    # calibration that TrainReport.step_times holds
    assert spans["trainer.step"]["total_s"] < sum(rep.step_times)
