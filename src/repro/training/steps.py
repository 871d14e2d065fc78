"""Jitted step builders: train / calibrate / eval — and the StepCache.

The paper's phase schedule changes the *compiled graph* (inject vs
bit-accurate model), so the driver holds one jitted step per distinct
graph and selects in Python — zero retracing during a run.
:class:`StepCache` is that holder: step functions are built lazily and
memoized under a key of ``(kind, resolved ApproxConfig, lr-scale,
microbatches)`` — the resolved config folds in the mode *and* the
site-backend spec, so arbitrary phase sequences (including repeated
visits to a mode and per-phase LR/microbatch overrides) each compile
exactly once per distinct graph, never per phase.

Microbatched gradient accumulation runs as a ``lax.scan`` over microbatch
slices; remat policy and approx mode are baked in at build time.

The memoization/trace-accounting core is :class:`CompiledFnCache`, which
also backs the serving engine's compiled step kinds (prefill / decode /
slot ops, keyed on ``(kind, slot shape, ApproxConfig)`` — see
:mod:`repro.runtime.engine`).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ApproxConfig, ModelConfig, TrainConfig, TrainMode
from repro.models.model import Model
from repro.optim import adamw_init, adamw_update
from repro.training.losses import accuracy, lm_loss


def init_train_state(
    model: Model, rng, approx: ApproxConfig,
    tcfg: Optional[TrainConfig] = None,
) -> Dict[str, Any]:
    params = model.init(rng)
    compress = tcfg.optim_compress if tcfg is not None else "none"
    return {
        "params": params,
        "opt": adamw_init(params, compress),
        "calib": model.init_calibration(approx),
        "step": jnp.zeros((), jnp.int32),
    }


def _loss_fn(params, batch, model: Model, approx, calib, rng, tcfg: TrainConfig,
             chip=None, backend_idx=None, bwd_gate=None):
    out = model.apply(
        params, batch, approx=approx, calib=calib, rng=rng, remat=tcfg.remat,
        chunk_q=tcfg.chunk_q, unroll=tcfg.scan_unroll,
        seq_shard=tcfg.seq_shard_activations, chip=chip, backend_idx=backend_idx,
        bwd_gate=bwd_gate,
    )
    logits = out.logits
    if model.cfg.frontend != "none":
        logits = logits[:, model.cfg.frontend_tokens :]
    loss = lm_loss(logits, batch["labels"])
    total = loss + 0.01 * out.aux_loss
    return total, {"loss": loss, "aux_loss": out.aux_loss, "logits_last": logits}


def _split_micro(batch, n: int):
    return jax.tree_util.tree_map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch
    )


def make_train_step(
    model: Model,
    approx: ApproxConfig,
    tcfg: TrainConfig,
    mode: Optional[TrainMode] = None,
    *,
    chip_aware: bool = False,
    switch_aware: bool = False,
    bwd_aware: bool = False,
):
    """Build a train step for a fixed approx mode (defaults to cfg's).

    ``chip_aware=True`` returns a step taking an extra trailing ``chip``
    argument (a :class:`repro.hw.variation.ChipProfile` pytree of runtime
    arrays) — variation-aware training: the emulated forward runs on that
    device instance.  The chip is a jit *argument*, so a whole fleet
    shares one compiled step.

    ``switch_aware=True`` adds a trailing ``backend_idx`` argument (a
    :mod:`repro.core.switch` index array / pytree): one-compile
    heterogeneous dispatch — the site→backend map is a jit argument, so
    every map (and every per-layer map) shares one compiled step.  Pass
    the *canonicalized* config (``switch.canonical``) so the cache key
    collapses too.

    ``bwd_aware=True`` adds a trailing ``bwd_gate`` argument (int32
    ``[n_sites]`` over ``switch.SITE_ORDER``): the approximate-backward
    gate — a runtime operand, so exact and gated-approx backward phases
    share ONE compiled step (exact passes a zeros mask).  Extra trailing
    arguments compose in flag order: ``(state, batch, rng[, chip]
    [, backend_idx][, bwd_gate])``.
    """
    if mode is not None:
        approx = dataclasses.replace(approx, mode=mode)

    def full_step(state, batch, rng, chip, backend_idx, bwd_gate):
        params, opt, calib = state["params"], state["opt"], state["calib"]
        n_micro = tcfg.microbatches

        def grad_one(p, mb, r):
            (total, metrics), grads = jax.value_and_grad(
                lambda q: _loss_fn(q, mb, model, approx, calib, r, tcfg, chip,
                                   backend_idx, bwd_gate),
                has_aux=True,
            )(p)
            metrics = {k: v for k, v in metrics.items() if k != "logits_last"}
            return grads, total, metrics

        if n_micro <= 1:
            grads, total, metrics = grad_one(params, batch, rng)
        else:
            micro = _split_micro(batch, n_micro)

            def body(acc, xs):
                mb, i = xs
                g, t, m = grad_one(params, mb, jax.random.fold_in(rng, i))
                acc_g, acc_t, acc_m = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), acc_g, g
                )
                return (acc_g, acc_t + t, jax.tree_util.tree_map(jnp.add, acc_m, m)), None

            zero_g = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params
            )
            zero_m = {"loss": jnp.zeros(()), "aux_loss": jnp.zeros(())}
            (grads, total, metrics), _ = jax.lax.scan(
                body, (zero_g, jnp.zeros(()), zero_m), (micro, jnp.arange(n_micro)),
                unroll=n_micro if tcfg.scan_unroll else 1,
            )
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
            total = total / n_micro
            metrics = jax.tree_util.tree_map(lambda m: m / n_micro, metrics)

        new_params, new_opt, opt_metrics = adamw_update(grads, opt, params, tcfg)
        metrics = dict(metrics, **opt_metrics, total_loss=total)
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "calib": calib,
            "step": state["step"] + 1,
        }
        return new_state, metrics

    if chip_aware and switch_aware and bwd_aware:
        return full_step

    def adapter(state, batch, rng, *extra):
        rest = list(extra)
        chip = rest.pop(0) if chip_aware else None
        backend_idx = rest.pop(0) if switch_aware else None
        bwd_gate = rest.pop(0) if bwd_aware else None
        return full_step(state, batch, rng, chip, backend_idx, bwd_gate)

    return adapter


def make_calibration_step(
    model: Model,
    approx: ApproxConfig,
    tcfg: TrainConfig,
    *,
    chip_aware: bool = False,
):
    """Forward-only pass with bit-accurate emulation that refreshes the
    error-injection statistics (paper Sec. 3.2 calibration batches).
    ``chip_aware=True`` adds a trailing ``chip`` argument: the stats then
    describe that device instance's error curves, not the nominal spec."""

    def chip_step(state, batch, rng, chip):
        out = model.apply(
            state["params"],
            batch,
            approx=approx,
            calib=state["calib"],
            rng=rng,
            collect=True,
            remat="none",
            chip=chip,
        )
        new_state = dict(state, calib=out.collected)
        logits = out.logits
        if model.cfg.frontend != "none":
            logits = logits[:, model.cfg.frontend_tokens :]
        return new_state, {"loss": lm_loss(logits, batch["labels"])}

    if chip_aware:
        return chip_step
    return lambda state, batch, rng: chip_step(state, batch, rng, None)


def make_eval_step(
    model: Model, approx: ApproxConfig, *, chip_aware: bool = False,
    switch_aware: bool = False,
):
    """Validation with bit-accurate emulation (paper validates with the
    accurate model — this is what the hardware would produce).
    ``chip_aware=True`` adds a trailing ``chip`` argument so a fleet of
    device instances can be hardware-evaled through one compiled step
    (the Pareto search's ensemble scoring).  ``switch_aware=True`` adds a
    trailing ``backend_idx`` argument (one-compile heterogeneous
    dispatch, see :mod:`repro.core.switch`); pass the canonicalized
    config — it has no approx backends of its own, so switch_aware also
    forces the MODEL-mode substitution."""
    eval_cfg = (
        dataclasses.replace(approx, mode=TrainMode.MODEL)
        if approx.approx_backends or switch_aware
        else approx
    )

    def full_step(state, batch, rng, chip, backend_idx):
        out = model.apply(
            state["params"], batch, approx=eval_cfg, calib=state["calib"],
            rng=rng, remat="none", chip=chip, backend_idx=backend_idx,
        )
        logits = out.logits
        if model.cfg.frontend != "none":
            logits = logits[:, model.cfg.frontend_tokens :]
        return {
            "loss": lm_loss(logits, batch["labels"]),
            "accuracy": accuracy(logits, batch["labels"]),
        }

    if chip_aware and switch_aware:
        return full_step
    if chip_aware:
        return lambda state, batch, rng, chip: full_step(
            state, batch, rng, chip, None
        )
    if switch_aware:
        return lambda state, batch, rng, backend_idx: full_step(
            state, batch, rng, None, backend_idx
        )
    return lambda state, batch, rng: full_step(state, batch, rng, None, None)


# ---------------------------------------------------------------------------
# Compiled-fn cache
# ---------------------------------------------------------------------------


class CompiledFnCache:
    """Lazily-built, memoized jitted functions keyed on the graph they
    compile — the zero-retrace machinery shared by training (one step per
    phase graph) and serving (one step per (kind, slot shape,
    ApproxConfig), see :mod:`repro.runtime.engine`).

    ``trace_counts`` increments at *trace* time (the counter bump runs
    inside the traced function body, which only executes when XLA
    retraces), so tests can assert a whole multi-phase training run or a
    churning serving workload compiled each graph exactly once.

    ``get`` is serialized by a lock: the serving fabric shares ONE cache
    across every engine replica (compile once, all replicas reuse), and
    threaded workers first-hitting the same key concurrently must not
    both build — a double build would jit the key twice and read as a
    phantom retrace in the fabric's zero-retrace accounting.
    """

    def __init__(self):
        self._fns: Dict[Tuple, Callable] = {}
        self.trace_counts: Dict[Tuple, int] = {}
        self._lock = threading.RLock()

    def get(self, key: Tuple, build: Callable[[], Callable], **jit_kwargs) -> Callable:
        """The jitted function for ``key``, building (``build()`` +
        ``jax.jit(..., **jit_kwargs)``) on first use."""
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                inner = build()

                def counted(*args, _inner=inner, _key=key):
                    # executes only while tracing: a retrace shows up here
                    self.trace_counts[_key] = self.trace_counts.get(_key, 0) + 1
                    return _inner(*args)

                fn = self._fns[key] = jax.jit(counted, **jit_kwargs)
        return fn

    def stats(self) -> Dict[str, Any]:
        """Compile-accounting summary (for reports / retrace guards)."""
        return {
            "built": len(self._fns),
            "traces": int(sum(self.trace_counts.values())),
            "retraces": int(
                sum(max(c - 1, 0) for c in self.trace_counts.values())
            ),
        }


class StepCache(CompiledFnCache):
    """Training-step cache for one model/run.

    The cache key is ``(kind, resolved ApproxConfig, lr_scale,
    microbatches, chip_aware, switch_aware, bwd_aware)``.  Chip-aware
    steps (variation-aware phases) take the device instance as a trailing
    runtime argument, so the key records only *that* a chip is threaded,
    never which one — a whole fleet shares one compiled step; likewise
    bwd-aware steps record only that a backward gate is threaded, so
    exact and gated-approx backward phases share one compiled step.  The
    resolved config is the run's ApproxConfig with
    the requested mode substituted — a frozen dataclass whose hash covers
    the mode, every per-backend params set, and the heterogeneous
    ``site_backends`` spec — so two phases that share a compiled graph
    share one entry, and any difference that changes the graph gets its
    own.
    """

    def __init__(self, model: Model, approx: ApproxConfig, tcfg: TrainConfig,
                 *, donate_state: bool = False):
        super().__init__()
        self.model = model
        self.approx = approx
        self.tcfg = tcfg
        # train/calibration steps consume their state argument: a caller
        # that holds no other reference to it (the Trainer) lets XLA write
        # the new state into the old one's buffers, instead of holding two
        # copies of params, master weights and moments at the step's peak
        self._donate = {"donate_argnums": (0,)} if donate_state else {}

    # ------------------------------------------------------------------
    def _resolve(self, mode: Optional[TrainMode]) -> ApproxConfig:
        if mode is None or mode == self.approx.mode:
            return self.approx
        return dataclasses.replace(self.approx, mode=mode)

    def _tcfg_for(self, lr_scale: float, microbatches: int) -> TrainConfig:
        if lr_scale == 1.0 and not microbatches:
            return self.tcfg
        return dataclasses.replace(
            self.tcfg,
            learning_rate=self.tcfg.learning_rate * lr_scale,
            microbatches=microbatches or self.tcfg.microbatches,
        )

    # ------------------------------------------------------------------
    def train(
        self,
        mode: Optional[TrainMode] = None,
        *,
        lr_scale: float = 1.0,
        microbatches: int = 0,
        chip_aware: bool = False,
        switch_aware: bool = False,
        bwd_aware: bool = False,
    ) -> Callable:
        approx = self._resolve(mode)
        if switch_aware:
            # one-compile dispatch: erase the backend map from the key —
            # every map of this mode shares the one compiled step; the
            # map rides in as the step's backend_idx argument
            from repro.core import switch as switch_lib

            approx = switch_lib.canonical(approx)
        key = ("train", approx, lr_scale, microbatches or self.tcfg.microbatches,
               chip_aware, switch_aware, bwd_aware)
        return self.get(
            key,
            lambda: make_train_step(
                self.model, approx, self._tcfg_for(lr_scale, microbatches),
                chip_aware=chip_aware, switch_aware=switch_aware,
                bwd_aware=bwd_aware,
            ),
            **self._donate,
        )

    def calibration(self, *, chip_aware: bool = False) -> Callable:
        # calibration stays static-dispatch: per-(site, backend) stat
        # shapes are part of the graph and cannot swap at runtime
        key = ("calibrate", self.approx, 1.0, self.tcfg.microbatches, chip_aware)
        return self.get(
            key,
            lambda: make_calibration_step(
                self.model, self.approx, self.tcfg, chip_aware=chip_aware
            ),
            **self._donate,
        )

    def eval(self, *, chip_aware: bool = False,
             switch_aware: bool = False) -> Callable:
        approx = self.approx
        if switch_aware:
            from repro.core import switch as switch_lib

            approx = switch_lib.canonical(approx)
        key = ("eval", approx, 1.0, self.tcfg.microbatches, chip_aware,
               switch_aware)
        return self.get(
            key, lambda: make_eval_step(self.model, approx,
                                        chip_aware=chip_aware,
                                        switch_aware=switch_aware)
        )

