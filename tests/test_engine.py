"""Serving-engine tests: slot-cache ops, bulk prefill, continuous-batching
decode consistency per family, zero-retrace churn, mixed-backend emulation.

Consistency tests follow test_models.py's teacher-forcing pattern: the
engine generates greedily, then the full-sequence forward on
prompt + generated must reproduce the engine's per-step logits.
"""
import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ApproxConfig, Backend, TrainMode
from repro.models import build_model
from repro.models import decode as D
from repro.runtime.engine import (
    Engine,
    Request,
    resolve_approx,
    run_static_baseline,
    synthetic_requests,
)

FAMILIES = ["qwen2.5-3b", "mamba2-130m", "zamba2-1.2b"]


def _model(arch):
    cfg = get_smoke_config(arch)
    if cfg.n_experts:
        # capacity drops differ between full-seq routing and decode; lift
        # capacity so the consistency comparison sees no drops
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _prompt(cfg, n, seed=5):
    return tuple(
        int(t)
        for t in jax.random.randint(
            jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size
        )
    )


# ---------------------------------------------------------------------------
# Slot-cache ops: admit/evict/reuse round-trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", FAMILIES)
def test_slot_roundtrip(arch):
    cfg, m, params = _model(arch)
    S = 16
    lane = m.init_cache(4, S)
    _, sub = m.prefill(params, jnp.asarray([_prompt(cfg, 6)]), max_seq=S)

    lane = m.slot_insert(lane, sub, jnp.int32(2))
    back = m.slot_extract(lane, 2, 1)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(sub)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # neighbours untouched
    for slot in (1, 3):
        for leaf in jax.tree_util.tree_leaves(m.slot_extract(lane, slot, 1)):
            assert float(jnp.abs(leaf).sum()) == 0.0

    # evict zeroes the slot; re-insert (reuse) restores it exactly
    lane = m.slot_reset(lane, jnp.int32(2))
    for leaf in jax.tree_util.tree_leaves(m.slot_extract(lane, 2, 1)):
        assert float(jnp.abs(leaf).sum()) == 0.0
    lane = m.slot_insert(lane, sub, jnp.int32(2))
    for a, b in zip(
        jax.tree_util.tree_leaves(m.slot_extract(lane, 2, 1)),
        jax.tree_util.tree_leaves(sub),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", FAMILIES)
def test_prefill_matches_apply(arch):
    """Bulk prefill's last-token logits == full forward at length-1, even
    when the prompt is right-padded to a larger bucket."""
    cfg, m, params = _model(arch)
    prompt = _prompt(cfg, 11)
    full = m.apply(params, {"tokens": jnp.asarray([prompt])})
    last, _ = m.prefill(params, jnp.asarray([prompt]), max_seq=24)
    np.testing.assert_allclose(
        np.asarray(last[0]), np.asarray(full.logits[0, -1]), rtol=2e-2, atol=3e-3
    )
    padded = jnp.asarray([list(prompt) + [3] * 5])  # garbage right-pad
    last_p, _ = m.prefill(
        params, padded, lengths=jnp.asarray([11]), max_seq=24
    )
    np.testing.assert_allclose(
        np.asarray(last_p), np.asarray(last), rtol=2e-2, atol=3e-3
    )


# ---------------------------------------------------------------------------
# Engine decode == full-sequence forward (per family)
# ---------------------------------------------------------------------------


def _assert_engine_matches_apply(cfg, m, params, result, prompt, approx=None):
    history = list(prompt) + result["tokens"][:-1]
    full = m.apply(
        params,
        {"tokens": jnp.asarray([history])},
        approx=approx if approx is not None else ApproxConfig(),
        rng=jax.random.PRNGKey(1),
    )
    start = len(prompt) - 1
    for i, row in enumerate(result["logits"]):
        np.testing.assert_allclose(
            row, np.asarray(full.logits[0, start + i]), rtol=2e-2, atol=3e-3,
            err_msg=f"step {i}",
        )


@pytest.mark.parametrize("arch", FAMILIES)
def test_engine_decode_matches_apply(arch):
    cfg, m, params = _model(arch)
    prompt = _prompt(cfg, 7)
    eng = Engine(m, params, n_slots=2, max_seq=32, collect_logits=True)
    res = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])
    assert len(res[0]["tokens"]) == 5
    _assert_engine_matches_apply(cfg, m, params, res[0], prompt)


@pytest.mark.slow
def test_engine_decode_matches_apply_moe():
    cfg, m, params = _model("dbrx-132b")
    prompt = _prompt(cfg, 7)
    eng = Engine(m, params, n_slots=1, max_seq=32, collect_logits=True)
    res = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=4)])
    _assert_engine_matches_apply(cfg, m, params, res[0], prompt)


# ---------------------------------------------------------------------------
# Zero retracing while requests churn through fixed slot shapes
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_zero_retrace_on_churn():
    cfg, m, params = _model("qwen2.5-3b")
    eng = Engine(m, params, n_slots=2, max_seq=48, min_bucket=8)
    queue = synthetic_requests(
        9, cfg.vocab_size, seed=3, prompt_lens=(3, 15), gen_lens=(2, 8),
        backends=("exact", "log_mult"),
    )
    res = eng.run(queue)
    assert len(res) == len(queue)
    stats = eng.compile_stats
    assert stats["retraces"] == 0, stats
    # bounded graph set: <= one decode per lane + one prefill per
    # (bucket, lane) + one shared slot-reset; prompts of 3..15 span
    # buckets {8, 16}
    assert stats["built"] <= 2 * (1 + 2) + 1, stats
    # slots were actually reused across the queue (churn happened)
    assert len(queue) > 2 * eng.n_slots


@pytest.mark.slow
def test_engine_queue_longer_than_slots_completes_all():
    cfg, m, params = _model("mamba2-130m")
    eng = Engine(m, params, n_slots=2, max_seq=32)
    queue = synthetic_requests(
        7, cfg.vocab_size, seed=11, prompt_lens=(2, 10), gen_lens=(1, 6)
    )
    res = eng.run(queue)
    assert sorted(res) == [q.rid for q in queue]
    for q in queue:
        assert len(res[q.rid]["tokens"]) == q.max_new_tokens


# ---------------------------------------------------------------------------
# Mixed-backend serving: per-request MODEL-mode logits match the oracles
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_mixed_backend_matches_oracles():
    cfg, m, params = _model("qwen2.5-3b")
    prompt = _prompt(cfg, 8)
    eng = Engine(m, params, n_slots=4, max_seq=32, collect_logits=True)
    queue = [
        Request(rid=0, prompt=prompt, max_new_tokens=4, backend="exact"),
        Request(rid=1, prompt=prompt, max_new_tokens=4, backend="log_mult"),
        Request(rid=2, prompt=prompt[:5], max_new_tokens=6, backend="log_mult"),
        Request(rid=3, prompt=prompt[:6], max_new_tokens=5, backend="approx_mult"),
    ]
    res = eng.run(queue)
    assert len(eng.lanes) == 3  # exact + log_mult + approx_mult
    oracles = {
        "exact": ApproxConfig(),
        "log_mult": ApproxConfig(backend=Backend.LOG_MULT, mode=TrainMode.MODEL),
        "approx_mult": ApproxConfig(
            backend=Backend.APPROX_MULT, mode=TrainMode.MODEL
        ),
    }
    for q in queue:
        assert res[q.rid]["emulated"] == (q.backend != "exact")
        _assert_engine_matches_apply(
            cfg, m, params, res[q.rid], q.prompt, approx=oracles[q.backend]
        )
    assert eng.compile_stats["retraces"] == 0


@pytest.mark.slow
def test_engine_mixed_site_request_runs():
    cfg, m, params = _model("qwen2.5-3b")
    prompt = _prompt(cfg, 6)
    eng = Engine(m, params, n_slots=2, max_seq=32, collect_logits=True)
    req = Request(
        rid=0, prompt=prompt, max_new_tokens=3,
        site_backends=(("attn_*", "sc"), ("mlp_*", "log_mult")),
    )
    res = eng.run([req])
    assert res[0]["emulated"]
    for row in res[0]["logits"]:
        assert np.isfinite(row).all()


def test_resolve_approx_lanes_and_validation():
    base = ApproxConfig()
    exact = resolve_approx(Request(rid=0, prompt=(1,), backend="exact"), base)
    assert not exact.active
    # emulate=False serves an approx-targeted request on the exact lane
    off = resolve_approx(
        Request(rid=1, prompt=(1,), backend="sc", emulate=False), base
    )
    assert off == exact
    emu = resolve_approx(Request(rid=2, prompt=(1,), backend="sc"), base)
    assert emu.active and emu.mode == TrainMode.MODEL
    with pytest.raises(KeyError):
        resolve_approx(Request(rid=3, prompt=(1,), backend="no_such_hw"), base)


def test_engine_evict_neutralizes_slots():
    """The moment a request finishes (others still running), its freed
    slot must hold nothing of it — token 0, pos 0, zero cache slice — so
    batch-coupled computations (MoE capacity, per-tensor sc/analog
    scales) never see serving history, only the canonical idle row."""
    cfg, m, params = _model("qwen2.5-3b")
    eng = Engine(m, params, n_slots=2, max_seq=24)
    prompt = _prompt(cfg, 5)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))   # finishes first
    eng.submit(Request(rid=1, prompt=prompt, max_new_tokens=10))
    while 0 not in eng.results:
        eng.step()
    assert 1 not in eng.results  # rid=1 still running
    (lane,) = eng.lanes.values()
    (slot,) = [i for i, s in enumerate(lane.slots) if s is None]
    assert int(lane.tokens[slot, 0]) == 0 and int(lane.pos[slot]) == 0
    for leaf in jax.tree_util.tree_leaves(
        D.slot_extract(cfg, lane.cache, slot, 1)
    ):
        assert float(jnp.abs(leaf).sum()) == 0.0


def test_engine_rejects_oversized_request():
    cfg, m, params = _model("qwen2.5-3b")
    eng = Engine(m, params, n_slots=1, max_seq=16)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=_prompt(cfg, 10), max_new_tokens=8))


# ---------------------------------------------------------------------------
# One-compile heterogeneous dispatch (switch=True): merged lanes, O(1) graphs
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_switch_merges_heterogeneous_lanes_one_graph():
    """N requests with N distinct site maps land in ONE merged lane and
    decode through ONE compiled graph (the per-slot index matrix is a
    runtime argument) — the static engine would build a lane + decode
    graph per distinct map."""
    cfg, m, params = _model("qwen2.5-3b")
    eng = Engine(m, params, n_slots=2, max_seq=32, min_bucket=8, switch=True)
    prompt = _prompt(cfg, 6)
    maps = [
        (("attn_*", "log_mult"),),
        (("mlp_*", "approx_mult"),),
        (("attn_q", "sc"), ("mlp_down", "log_mult")),
        (("*", "analog"),),
    ]
    queue = [
        Request(rid=i, prompt=prompt, max_new_tokens=3,
                site_backends=maps[i % len(maps)])
        for i in range(6)
    ]
    queue.append(Request(rid=99, prompt=prompt, max_new_tokens=2))  # exact
    res = eng.run(queue)
    assert sorted(res) == sorted(q.rid for q in queue)
    # one merged emulated lane + the exact requests' own static lane
    assert len(eng.lanes) == 2
    stats = eng.compile_stats
    assert stats["retraces"] == 0, stats
    decode_switch = [k for k in eng.fns.trace_counts if k[0] == "decode_switch"]
    assert len(decode_switch) == 1
    assert eng.fns.trace_counts[decode_switch[0]] == 1
    # one prompt bucket -> one switch prefill graph for every map
    prefill_switch = [k for k in eng.fns.trace_counts if k[0] == "prefill_switch"]
    assert len(prefill_switch) == 1
    assert eng.metrics()["switch"] is True


@pytest.mark.slow
def test_engine_switch_solo_matches_static_oracle():
    """A lone per-row-scale request decodes through the merged switch
    lane to the same tokens and float32-ulp-identical logits as the
    static lane.  Each projection is bitwise-equal between the paths
    (tests/test_dispatch.py), but XLA fuses the inlined static
    emulation into surrounding ops while a lax.switch branch is a call
    boundary it cannot fuse across, so whole-graph logits round apart
    at ~1e-7.  (Per-tensor-scale sc / analog are additionally only
    solo-exact at batch 1 — documented caveat.)"""
    cfg, m, params = _model("qwen2.5-3b")
    prompt = _prompt(cfg, 6)

    def req():
        return Request(rid=0, prompt=prompt, max_new_tokens=4,
                       backend="log_mult")

    e1 = Engine(m, params, n_slots=2, max_seq=32, collect_logits=True)
    r1 = e1.run([req()])
    e2 = Engine(m, params, n_slots=2, max_seq=32, collect_logits=True,
                switch=True)
    r2 = e2.run([req()])
    assert r1[0]["tokens"] == r2[0]["tokens"]
    for i, (a, b) in enumerate(zip(r1[0]["logits"], r2[0]["logits"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                   err_msg=f"step {i}")


def test_engine_switch_rejects_fleet_and_moe():
    from repro.hw import Fleet

    cfg, m, params = _model("qwen2.5-3b")
    with pytest.raises(ValueError, match="incompatible with a fleet"):
        Engine(m, params, n_slots=1, max_seq=16, switch=True, fleet=Fleet(2))
    from repro.models import build_model
    from repro.configs import get_smoke_config

    moe = build_model(get_smoke_config("dbrx-132b"))
    with pytest.raises(ValueError, match="MoE"):
        Engine(moe, None, n_slots=1, max_seq=16, switch=True)


# ---------------------------------------------------------------------------
# Warm-start: newly bound chips seed correction from the fleet mean
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_engine_warm_start_seeds_from_fleet_mean():
    from repro.hw import Fleet, VariationModel

    cfg, m, params = _model("qwen2.5-3b")
    prompt = _prompt(cfg, 5)
    fleet = Fleet(2, seed=3, variation=VariationModel())

    def reqs(n):
        return [
            Request(rid=i, prompt=prompt, max_new_tokens=2,
                    backend="log_mult")
            for i in range(n)
        ]

    # cold fleet: warm_start falls back to the bind-time collect fit
    e1 = Engine(m, params, n_slots=1, max_seq=16, fleet=fleet,
                warm_start=True, seed=0)
    e1.run(reqs(2))
    assert e1.recalibrations >= 1
    assert fleet.calibrated_ids()

    # calibrated fleet: binding is probe-only — the lane starts with the
    # fleet-mean polynomials and ZERO bind-time recalibrations
    e2 = Engine(m, params, n_slots=1, max_seq=16, fleet=fleet,
                warm_start=True, seed=0)
    e2.run(reqs(1))
    assert e2.recalibrations == 0
    lane = next(l for l in e2.lanes.values() if l.chip is not None)
    assert lane.recals == 0
    assert lane.calib is not None
    assert lane.probe_losses  # raw probe still recorded (drift baseline)
    assert lane.corrected_losses  # and the serving-quality signal


# ---------------------------------------------------------------------------
# Static baseline (timing-fixed legacy driver) still serves correctly
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_static_baseline_reports_compile_separately():
    cfg, m, params = _model("qwen2.5-3b")
    queue = synthetic_requests(
        4, cfg.vocab_size, seed=2, prompt_lens=(6, 6), gen_lens=(4, 4)
    )
    rep = run_static_baseline(m, params, queue, batch=2)
    assert rep["compile_s"] > 0.0  # first step traced outside the timers
    assert rep["prefill_s"] > 0.0 and rep["decode_s"] > 0.0
    assert sorted(rep["outputs"]) == [q.rid for q in queue]
    for q in queue:
        assert len(rep["outputs"][q.rid]) == q.max_new_tokens


# ---------------------------------------------------------------------------
# Spans: the engine's timers are the span totals
# ---------------------------------------------------------------------------


def _requests(cfg, rid0, lengths, gen=3):
    return [
        Request(rid=rid0 + i, prompt=_prompt(cfg, n, seed=rid0 + i),
                max_new_tokens=gen)
        for i, n in enumerate(lengths)
    ]


@pytest.mark.slow
def test_engine_timers_are_span_totals():
    from repro.runtime.spans import delta

    cfg, m, params = _model("qwen2.5-3b")
    eng = Engine(m, params, n_slots=2, max_seq=48, min_bucket=8)
    warm = _requests(cfg, 0, (5, 12))  # buckets 8 and 16: every graph traces
    eng.run(warm)
    before, steps0 = eng.spans.snapshot(), eng.decode_steps
    queue = _requests(cfg, 100, (4, 7, 11, 14, 3))
    eng.run(queue)
    snap = eng.spans.snapshot()
    spans = snap["spans"]

    # the timers read the totals; a call that traced is in compile_s only
    assert eng.prefill_s == spans["engine.prefill"]["total_s"]
    assert eng.decode_s == spans["engine.decode"]["total_s"]
    assert eng.compile_s == spans["engine.compile"]["total_s"]
    assert eng.compile_s == sum(s for _, s in eng.compiles)
    compiled = [k[0] for k, _ in eng.compiles]
    assert sorted(set(compiled)) == ["decode", "prefill", "reset"]
    assert spans["engine.compile"]["count"] == len(compiled)
    assert (spans["engine.prefill"]["count"] + compiled.count("prefill")
            == len(warm) + len(queue))
    assert (spans["engine.decode"]["count"] + compiled.count("decode")
            == eng.decode_steps)
    assert (spans["engine.reset"]["count"] + compiled.count("reset")
            == len(eng.results))
    assert sum(r["prefill_s"] for r in eng.results.values()) == pytest.approx(
        eng.prefill_s, rel=1e-9)

    # the second run is warm: its change holds no compile, one decode span
    # per decode step and one reset per finished request
    d = delta(before, snap)
    assert "engine.compile" not in d["spans"]
    assert d["spans"]["engine.decode"]["count"] == eng.decode_steps - steps0
    assert d["spans"]["engine.reset"]["count"] == len(queue)
    assert d["spans"]["engine.prefill"]["count"] == len(queue)
    assert d["counts"]["engine.admitted"] == len(queue)
    assert d["counts"]["engine.scanned"] >= len(queue)
    for kind in ("prefill", "decode", "reset"):
        s = d["spans"][f"engine.{kind}"]
        kids = (d["spans"][f"engine.{kind}.dispatch"]["total_s"]
                + d["spans"][f"engine.{kind}.wait"]["total_s"])
        assert s["self_s"] == pytest.approx(s["total_s"] - kids, abs=1e-9)
    # the admission loop's self time leaves out the prefills it makes
    admit = d["spans"]["engine.admit"]
    assert 0.0 <= admit["self_s"]
    assert admit["self_s"] < admit["total_s"] - d["spans"]["engine.prefill"]["total_s"]
    assert d["spans"]["engine.step"]["count"] == d["spans"]["engine.admit"]["count"]


@pytest.mark.slow
def test_engine_spans_in_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    cfg, m, params = _model("qwen2.5-3b")
    eng = Engine(m, params, n_slots=2, max_seq=48, min_bucket=8)
    eng.run(_requests(cfg, 0, (5,)))  # compile outside the trace
    decodes0 = eng.spans.total("engine.decode"), eng.decode_steps
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(_requests(cfg, 10, (6, 3)))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))

    def inside(inner, outer):
        return all(any(a <= s and e <= b for a, b, _ in events[outer])
                   for s, e, _ in events[inner])

    assert len(events["engine.decode"]) == eng.decode_steps - decodes0[1]
    assert eng.spans.total("engine.decode") > decodes0[0]
    assert inside("engine.decode", "engine.step")
    assert inside("engine.decode.wait", "engine.decode")
    assert inside("engine.admit", "engine.step")
    assert inside("engine.prefill", "engine.admit")
    assert {(s["rid"], s["bucket"]) for _, _, s in events["engine.prefill"]} == {
        (10, 8), (11, 8)}


# ---------------------------------------------------------------------------
# Admission from per-lane-key queues: the order of a whole-queue scan
# ---------------------------------------------------------------------------


class _ScanEngine(Engine):
    """The oracle: one FIFO of every pending request, all of it popped
    each step, what fits admitted and the rest pushed back in order."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.pending = deque()

    def submit(self, req):
        self.pending.append((req, resolve_approx(req, self.approx_base)))

    def _admit_pending(self):
        events, deferred = [], deque()
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
        return events


def _admission_log(eng):
    """Record, step by step, each admission's (rid, lane, slot)."""
    log = []
    admit, step = eng._admit, eng.step

    def rec_admit(lane, slot, req, approx=None):
        log[-1].append((req.rid, lane.key, slot))
        return admit(lane, slot, req, approx)

    def rec_step():
        log.append([])
        return step()

    eng._admit, eng.step = rec_admit, rec_step
    return log


def _mixed_queue(cfg, spec):
    return [
        Request(rid=i, prompt=_prompt(cfg, 3 + i % 6, seed=40 + i),
                max_new_tokens=gen, temperature=0.8 if i % 3 == 0 else 0.0,
                **kw)
        for i, (gen, kw) in enumerate(spec)
    ]


_EXACT, _SC, _AM = {}, {"backend": "sc"}, {"backend": "approx_mult"}
_QUEUES = {
    # three lane keys; the exact key saturates its two slots while sc and
    # approx_mult have room, and requests of one token free their slot
    # inside admission
    "mixed": (
        {},
        [(3, _EXACT), (1, _EXACT), (2, _SC), (4, _EXACT), (1, _EXACT),
         (1, _AM), (2, _EXACT), (1, _SC), (3, _AM), (3, _SC), (1, _EXACT),
         (2, _AM), (1, _SC), (2, _EXACT)],
    ),
    # switch: every emulated map shares one merged key, exact its own
    "switch": (
        {"switch": True},
        [(2, {"site_backends": (("attn_*", "log_mult"),)}), (1, _EXACT),
         (3, {"site_backends": (("mlp_*", "approx_mult"),)}), (2, _EXACT),
         (1, {"backend": "log_mult"}), (2, _EXACT),
         (2, {"site_backends": (("attn_q", "sc"),)}), (1, _EXACT),
         (3, {"backend": "approx_mult"})],
    ),
    # fleet: one emulated key grows a lane per chip; exact keeps one lane
    "fleet": (
        {"fleet": 2},
        [(2, {"backend": "log_mult"}), (3, {"backend": "log_mult"}),
         (1, _EXACT), (2, {"backend": "log_mult"}), (1, {"backend": "log_mult"}),
         (2, _EXACT), (2, {"backend": "log_mult"}), (1, _EXACT)],
    ),
}


@pytest.mark.parametrize("queue_name", sorted(_QUEUES))
def test_engine_admission_order_matches_queue_scan(queue_name):
    from repro.hw import Fleet
    from repro.training.steps import CompiledFnCache

    cfg, m, params = _model("qwen2.5-3b")
    kwargs, spec = _QUEUES[queue_name]
    queue = _mixed_queue(cfg, spec)
    fns = CompiledFnCache()  # compile once for both engines

    def make(cls):
        kw = dict(kwargs)
        if "fleet" in kw:  # a fleet of its own: serving ages its chips
            kw["fleet"] = Fleet(kw["fleet"], seed=3)
        slots = 1 if "fleet" in kw else 2
        return cls(m, params, n_slots=slots, max_seq=16, min_bucket=8,
                   seed=7, fns=fns, **kw)

    new, old = make(Engine), make(_ScanEngine)
    log_new, log_old = _admission_log(new), _admission_log(old)
    res_new, res_old = new.run(queue), old.run(queue)

    assert log_new == log_old
    assert len({rid for step in log_new for rid, _, _ in step}) == len(queue)
    assert sum(1 for step in log_new if len(step) > 1) > 0
    assert {rid: r["tokens"] for rid, r in res_new.items()} == {
        rid: r["tokens"] for rid, r in res_old.items()}
    assert {rid: r["chip"] for rid, r in res_new.items()} == {
        rid: r["chip"] for rid, r in res_old.items()}
    assert new._tick == old._tick
    if queue_name == "fleet":
        assert sum(1 for (a, _) in new.lanes if a.active) == 2


def test_engine_scan_examines_heads_not_the_backlog():
    cfg, m, params = _model("qwen2.5-3b")
    eng = Engine(m, params, n_slots=4, max_seq=16, min_bucket=8)
    queue = [
        Request(rid=i, prompt=_prompt(cfg, 3 + i % 4, seed=i),
                max_new_tokens=1 + i % 3,
                backend=("exact", "log_mult")[i % 2])
        for i in range(200)
    ]
    for r in queue:
        eng.submit(r)
    n_keys = 2
    admitted_total = 0
    while eng.pending or any(l.n_active() for l in eng.lanes.values()):
        waiting = len(eng.pending)
        before = eng.spans.snapshot()["counts"]
        eng.step()
        after = eng.spans.snapshot()["counts"]
        scanned = after["engine.scanned"] - before.get("engine.scanned", 0)
        admitted = after["engine.admitted"] - before.get("engine.admitted", 0)
        assert admitted <= scanned <= admitted + n_keys
        assert len(eng.pending) == waiting - admitted
        if waiting > 2 * eng.n_slots * n_keys + n_keys:
            assert scanned < waiting
        admitted_total += admitted
    assert admitted_total == len(queue)
    assert sorted(eng.results) == list(range(len(queue)))


def test_pending_queue_interface():
    from repro.runtime.engine import PendingQueue

    a = ApproxConfig()
    b = ApproxConfig(backend=Backend.LOG_MULT, mode=TrainMode.MODEL)
    reqs = [Request(rid=i, prompt=(1,)) for i in range(7)]
    keys = [(a, False), (b, False), (b, False), (a, False), (b, True),
            (a, False), (b, False)]
    pq = PendingQueue()
    assert len(pq) == 0 and not pq and list(pq) == []
    for r, k in zip(reqs, keys):
        pq.append(k, r, k[0])
    assert len(pq) == 7 and pq
    # one FIFO's order across the keys, each request with its config
    assert [(r.rid, ap) for r, ap in pq] == [
        (i, k[0]) for i, k in enumerate(keys)]
    # admission pops by key from a heap of the keys' oldest requests
    heads = pq.heads()
    assert sorted(heads) == [(0, (a, False)), (1, (b, False)), (4, (b, True))]
    req, ap, nxt = pq.popleft((b, False))
    assert (req.rid, ap, nxt) == (1, b, 2) and len(pq) == 6
    req, _, nxt = pq.popleft((b, True))
    assert req.rid == 4 and nxt is None
    assert [r.rid for r, _ in pq] == [0, 2, 3, 5, 6]
    assert {k for _, k in pq.heads()} == {(a, False), (b, False)}
    pq.clear()
    assert len(pq) == 0 and not pq and list(pq) == [] and pq.heads() == []
    # submission order carries on after a clear
    pq.append((b, False), reqs[0], b)
    pq.append((a, False), reqs[1], a)
    assert [r.rid for r, _ in pq] == [0, 1]
