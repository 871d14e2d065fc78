"""Approximate-backward training (gated int8 gradients) + quantized
optimizer state.

Covers ISSUE 8's acceptance surface at test scale:

* a zeros gate mask matches no gate at all (to float-fusion precision),
  and the gate never touches forward values — the plumbing is inert
  until opened;
* gate-open gradients stay directionally aligned with the exact backward
  for every registered backend (hypothesis property over data seeds);
* flipping ``Phase(backward=...)`` and the runtime gate mask mid-run
  never retraces — one compiled train step serves every backward mode;
* bf16-momentum / SM3-factored optimizer state survives the checkpoint
  round-trip bitwise and resumes deterministically (the stochastic
  rounding is keyed on the step count, not an ambient seed);
* bf16 error-feedback buffers keep the compressed cross-pod reduction
  convergent on a toy GD loop.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, PartitionSpec as P

from repro.ckpt import CheckpointManager
from repro.configs import get_smoke_config
from repro.configs.base import (
    AnalogParams,
    ApproxConfig,
    Backend,
    Phase,
    SCParams,
    TrainConfig,
    TrainMode,
)
from repro.core import switch
from repro.data import SyntheticLM
from repro.models import build_model
from repro.optim import state_bytes
from repro.optim.compress import init_compression_state, int8_allreduce
from repro.runtime.trainer import Trainer
from repro.training import steps as step_lib
from repro.training.steps import _loss_fn

BACKENDS = (Backend.SC, Backend.APPROX_MULT, Backend.ANALOG, Backend.LOG_MULT)
N_SITES = len(switch.SITE_ORDER)

CFG = get_smoke_config("paper-tinyconv")
MODEL = build_model(CFG)
DATA = SyntheticLM(CFG.vocab_size, 16, 2, seed=3)
TCFG = TrainConfig(total_steps=8, warmup_steps=1, learning_rate=1e-3)


@pytest.fixture(scope="module")
def params():
    return MODEL.init(jax.random.PRNGKey(0))


def _approx_cfg(backend: Backend) -> ApproxConfig:
    return ApproxConfig(
        backend=backend, mode=TrainMode.INJECT,
        analog=AnalogParams(array_size=min(32, CFG.d_model)),
        sc=SCParams(bits=64), calibrate_every=4,
    )


_GRAD_FNS = {}


def _grad_fn(backend: Backend):
    """One jitted grad fn per backend; the gate is a runtime argument so
    exact (zeros) and approx (ones) backward share the single trace."""
    if backend not in _GRAD_FNS:
        approx = _approx_cfg(backend)
        calib = MODEL.init_calibration(approx)

        def gfn(p, batch, rng, gate):
            return jax.grad(
                lambda q: _loss_fn(q, batch, MODEL, approx, calib, rng, TCFG,
                                   bwd_gate=gate)[0]
            )(p)

        _GRAD_FNS[backend] = jax.jit(gfn)
    return _GRAD_FNS[backend]


def _flat(tree):
    return jnp.concatenate(
        [jnp.ravel(x).astype(jnp.float32)
         for x in jax.tree_util.tree_leaves(tree)]
    )


@pytest.mark.parametrize("backend", BACKENDS, ids=lambda b: b.value)
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 31))
def test_gated_grads_track_exact(params, backend, seed):
    """Gate-open gradients (int8 surrogate VJP) keep the exact backward's
    direction for every registered backend and any data batch."""
    batch = DATA.batch_at(seed)
    rng = jax.random.fold_in(jax.random.PRNGKey(9), seed)
    gfn = _grad_fn(backend)
    g_exact = _flat(gfn(params, batch, rng, jnp.zeros(N_SITES, jnp.int32)))
    g_approx = _flat(gfn(params, batch, rng, jnp.ones(N_SITES, jnp.int32)))
    assert bool(jnp.isfinite(g_approx).all())
    # the gate must actually reroute something...
    assert bool(jnp.any(g_exact != g_approx))
    # ...without losing the descent direction
    cos = jnp.vdot(g_exact, g_approx) / (
        jnp.linalg.norm(g_exact) * jnp.linalg.norm(g_approx) + 1e-12
    )
    assert float(cos) > 0.9, f"{backend.value}: cosine {float(cos):.4f}"


def test_zero_gate_equals_ungated(params):
    """A zeros mask takes the exact-backward cond branch everywhere: the
    gradients must match the unplumbed (gate=None) path to float-fusion
    precision (the ``lax.cond`` wrapper changes XLA fusion, not math —
    bitwise equality across distinct compiled graphs is not an XLA
    guarantee)."""
    approx = _approx_cfg(Backend.APPROX_MULT)
    calib = MODEL.init_calibration(approx)
    batch = DATA.batch_at(0)
    rng = jax.random.PRNGKey(5)

    def loss(q, gate):
        return _loss_fn(q, batch, MODEL, approx, calib, rng, TCFG,
                        bwd_gate=gate)[0]

    g_none = jax.grad(lambda q: loss(q, None))(params)
    g_zero = _grad_fn(Backend.APPROX_MULT)(
        params, batch, rng, jnp.zeros(N_SITES, jnp.int32)
    )
    for a, b in zip(jax.tree_util.tree_leaves(g_none),
                    jax.tree_util.tree_leaves(g_zero)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-5
        )


def test_gate_never_touches_forward(params):
    """The gate reroutes VJPs only: forward logits are bitwise identical
    whether the mask is absent, closed, or fully open."""
    approx = _approx_cfg(Backend.APPROX_MULT)
    calib = MODEL.init_calibration(approx)
    batch = DATA.batch_at(1)
    rng = jax.random.PRNGKey(6)

    def logits(gate):
        out = MODEL.apply(params, batch, approx=approx, calib=calib, rng=rng,
                          bwd_gate=gate)
        return np.asarray(out.logits)

    base = logits(None)
    np.testing.assert_array_equal(base, logits(jnp.zeros(N_SITES, jnp.int32)))
    np.testing.assert_array_equal(base, logits(jnp.ones(N_SITES, jnp.int32)))


def test_backward_mode_flips_never_retrace(tmp_path):
    """exact -> approx -> auto -> exact backward across phases (plus the
    auto phase's mid-phase gate refreshes) through one Trainer run: every
    graph compiles exactly once."""
    approx = _approx_cfg(Backend.APPROX_MULT)
    phases = (
        Phase.exact(2),
        dataclasses.replace(Phase.inject(3), backward="approx",
                            gate_frac=0.5),
        dataclasses.replace(Phase.inject(4), backward="auto",
                            gate_frac=0.75, gate_every=2),
        Phase.inject(2),
    )
    tcfg = TrainConfig(
        total_steps=11, warmup_steps=1, learning_rate=1e-3,
        phases=phases, checkpoint_every=100,
    )
    tr = Trainer(MODEL, approx, tcfg, DATA, str(tmp_path))
    rep = tr.run()
    assert rep.backward_steps == {"exact": 4, "approx": 3, "auto": 4}
    # approx phase derives once; auto phase re-derives every gate_every
    assert rep.gate_refreshes >= 3
    assert rep.compile_stats["retraces"] == 0, rep.compile_stats
    assert rep.compile_stats["built"] == rep.compile_stats["traces"]
    # the derived masks gate sites open (frac > 0 with >= 1 model site)
    assert all(n > 0 for _, n in rep.gate_events)


@pytest.mark.parametrize("compress", ["bf16", "sm3"])
def test_compressed_opt_checkpoint_roundtrip(tmp_path, compress):
    """bf16 momentum / SM3-factored second moments survive the checkpoint
    round-trip bitwise, and the resumed run is bitwise the unbroken one."""
    approx = ApproxConfig()
    tcfg = dataclasses.replace(TCFG, optim_compress=compress)
    state = step_lib.init_train_state(
        MODEL, jax.random.PRNGKey(0), approx, tcfg
    )
    train = jax.jit(step_lib.make_train_step(MODEL, approx, tcfg))
    for s in range(3):
        state, _ = train(state, DATA.batch_at(s),
                         jax.random.fold_in(jax.random.PRNGKey(1), s))

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, state, blocking=True)
    restored = mgr.restore(state)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # one more step from the live state vs the restored state: identical
    # bit for bit (stochastic rounding is keyed on opt["count"])
    batch = DATA.batch_at(3)
    rng = jax.random.fold_in(jax.random.PRNGKey(1), 3)
    live, _ = train(state, batch, rng)
    resumed, _ = train(restored, batch, rng)
    for a, b in zip(jax.tree_util.tree_leaves(live),
                    jax.tree_util.tree_leaves(resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # and the compression is real: strictly fewer resident bytes than fp32
    full = step_lib.init_train_state(
        MODEL, jax.random.PRNGKey(0), approx, dataclasses.replace(
            TCFG, optim_compress="none")
    )
    assert state_bytes(state["opt"]) < state_bytes(full["opt"])


def test_backward_macs_and_energy_pricing():
    """dryrun counts backward MACs at 2x forward; backward_map_energy
    prices gated-open sites at INT8_BWD_MAC_ENERGY and exact at 1.0,
    accepting both the runtime [S] mask and a {site: 0/1} mapping."""
    from repro.search import costmodel

    costs = costmodel.site_costs(CFG, seq_len=4, batch=2)
    for c in costs.values():
        assert c["bwd_macs"] == 2.0 * c["macs"]

    approx = _approx_cfg(Backend.APPROX_MULT)
    e_exact = costmodel.backward_map_energy(CFG, approx, gate=None,
                                            costs=costs)
    assert e_exact == sum(c["bwd_macs"] for c in costs.values())
    all_open = np.ones(N_SITES, np.int32)
    e_open = costmodel.backward_map_energy(CFG, approx, gate=all_open,
                                           costs=costs)
    assert e_open == pytest.approx(costmodel.INT8_BWD_MAC_ENERGY * e_exact)
    # mask and mapping forms agree
    e_map = costmodel.backward_map_energy(
        CFG, approx, gate={s: 1 for s in costs}, costs=costs
    )
    assert e_map == pytest.approx(e_open)
    # a training step composes forward (backend-priced) + backward
    total = costmodel.train_map_energy(CFG, approx, gate=all_open,
                                       costs=costs)
    fwd = costmodel.map_energy(CFG, approx, costs=costs)
    assert total == pytest.approx(fwd + e_open)
    with pytest.raises(ValueError):
        costmodel.backward_map_energy(CFG, approx, gate=np.ones(3, np.int32),
                                      costs=costs)


def test_bf16_error_feedback_converges():
    """Toy GD through the int8 cross-pod reduction with bf16 error
    feedback: converges to the optimum; residuals stay bf16 and bounded."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("pod",))
    target = jax.random.normal(jax.random.PRNGKey(2), (64,))
    w = jnp.zeros((64,))
    ef = init_compression_state({"w": w}, "int8")["w"]
    assert ef.dtype == jnp.bfloat16  # bf16 buffers are the default

    def body(g, e):
        out, e2 = int8_allreduce(g[0], e[0], "pod")
        return out[None], e2[None]

    reduce = jax.shard_map(
        body, mesh=mesh, in_specs=(P("pod"), P("pod")),
        out_specs=(P("pod"), P("pod")), check_vma=False,
    )

    @jax.jit
    def step(w, ef):
        g = w - target  # grad of 0.5 * ||w - target||^2
        rg, ef2 = reduce(g[None], ef[None])
        return w - 0.5 * rg[0], ef2[0]

    for _ in range(80):
        w, ef = step(w, ef)
    assert ef.dtype == jnp.bfloat16
    assert float(jnp.abs(w - target).max()) < 1e-2
    assert float(jnp.abs(ef.astype(jnp.float32)).max()) < 0.05
