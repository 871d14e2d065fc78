"""Serving-fabric tests: router policy units, bounded admission +
backpressure, replica death re-homing, recal pushes at step boundaries,
SLO drain-and-retire, and the fleet-global drift-age agreement.

Fabrics here run the deterministic sync pump (threads=False): same
fits, same placement, every run — the threaded drive mode gets one
smoke test at the end.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.hw import DriftModel, Fleet, VariationModel
from repro.models import build_model
from repro.runtime.engine import Engine, Request, synthetic_requests
from repro.serving import (
    Fabric,
    ReplicaSnapshot,
    Router,
    RouterPolicy,
    RoundRobinRouter,
)
from repro.training.steps import CompiledFnCache


@pytest.fixture(scope="module")
def tiny():
    cfg = get_smoke_config("paper-tinyconv")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fns():
    # one compile cache for the whole module: every fabric/engine below
    # shares it, so each (graph, shape) traces once across all tests
    return CompiledFnCache()


@pytest.fixture(scope="module")
def probe(tiny):
    # a tiny probe batch: recalibration fits are full collect passes
    # over it, and these tests exercise the *plumbing*, not fit quality
    cfg, _, _ = tiny
    rnd = np.random.default_rng(9)
    return {
        "tokens": rnd.integers(0, cfg.vocab_size, (1, 8), np.int32),
        "labels": rnd.integers(0, cfg.vocab_size, (1, 8), np.int32),
    }


def _queue(cfg, n, seed=1, backends=("exact", "log_mult"), gen=(3, 6)):
    return synthetic_requests(
        n, cfg.vocab_size, seed=seed, prompt_lens=(3, 8), gen_lens=gen,
        backends=backends,
    )


# ---------------------------------------------------------------------------
# Router policy units (pure host logic, no jax)
# ---------------------------------------------------------------------------


def _snap(wid, **kw):
    base = dict(wid=wid, alive=True, queue_depth=0, queue_capacity=4,
                slot_util=0.0, worst_corrected_loss=0.0,
                awaiting_recal=False)
    base.update(kw)
    return ReplicaSnapshot(**base)


def test_router_prefers_healthy_and_parks_tolerant_on_stale():
    r = Router(RouterPolicy())
    quality = Request(rid=0, prompt=(1, 2), max_new_tokens=2)
    tolerant = dataclasses.replace(quality, rid=1, latency_tolerant=True)
    snaps = [_snap(0, awaiting_recal=True), _snap(1)]
    # quality traffic avoids the stale replica; tolerant traffic is
    # parked there (it keeps earning while the recal service catches up)
    assert r.select(snaps, quality) == (1, None)
    assert r.select(snaps, tolerant) == (0, None)
    # load still matters: a healthy replica with a full queue loses to a
    # healthy empty one
    snaps = [_snap(0, queue_depth=3), _snap(1)]
    assert r.select(snaps, quality) == (1, None)
    # ... and health dominates mild load differences
    snaps = [_snap(0, worst_corrected_loss=3.0), _snap(1, queue_depth=1)]
    assert r.select(snaps, quality) == (1, None)


def test_router_backpressure_codes():
    r = Router()
    req = Request(rid=0, prompt=(1,), max_new_tokens=1)
    # every inbox full -> SATURATED; nothing alive -> NO_REPLICA
    full = [_snap(0, queue_depth=4), _snap(1, queue_depth=4)]
    assert r.select(full, req) == (None, "SATURATED")
    dead = [_snap(0, alive=False), _snap(1, alive=False)]
    assert r.select(dead, req) == (None, "NO_REPLICA")
    assert r.stats()["rejected"] == {"SATURATED": 1, "NO_REPLICA": 1}


def test_router_slo_escalation_ladder():
    r = Router(RouterPolicy(slo_loss=1.0, slo_patience=3))
    # breaches must be CONSECUTIVE: a healthy probe resets the count
    assert r.observe_probe(0, 2.0) is None
    assert r.observe_probe(0, 2.0) is None
    assert r.observe_probe(0, 0.5) is None
    assert r.observe_probe(0, 2.0) is None
    assert r.observe_probe(0, 2.0) is None
    assert r.observe_probe(0, 2.0) == "demote"      # rung 0
    assert r.observe_probe(0, 2.0) is None           # count restarted
    assert r.observe_probe(0, 2.0) is None
    assert r.observe_probe(0, 2.0) == "retire"       # rung 1
    # SLO disabled (the default): never escalates
    off = Router()
    assert all(off.observe_probe(1, 99.0) is None for _ in range(10))
    # no demote rung configured: first escalation retires
    direct = Router(RouterPolicy(slo_loss=1.0, slo_patience=1,
                                 demote_sites=None))
    assert direct.observe_probe(2, 5.0) == "retire"


def test_round_robin_cycles():
    r = RoundRobinRouter()
    req = Request(rid=0, prompt=(1,), max_new_tokens=1)
    snaps = [_snap(0, awaiting_recal=True), _snap(1)]
    picks = [r.select(snaps, req)[0] for _ in range(4)]
    assert picks == [0, 1, 0, 1]  # health-blind by construction


# ---------------------------------------------------------------------------
# Fabric: admission, death, recal pushes, retirement
# ---------------------------------------------------------------------------


def test_fabric_saturation_bounded_queue_and_reject(tiny, fns):
    cfg, model, params = tiny
    fab = Fabric(model, params, replicas=2, n_slots=1, max_seq=32,
                 queue_depth=2, fns=fns)
    try:
        out = [fab.submit(r) for r in _queue(cfg, 8, backends=("exact",))]
        admitted = [o for o in out if o["admitted"]]
        rejected = [o for o in out if not o["admitted"]]
        # 2 replicas x depth 2: exactly 4 fit, the rest bounce with the
        # backpressure code (clients retry with backoff)
        assert len(admitted) == 4
        assert rejected and all(o["code"] == "SATURATED" for o in rejected)
        # rejected work isn't lost to the fabric's counters
        assert fab.fabric_report()["rejected_saturated"] == len(rejected)
        # the admitted four still complete
        res = fab.run()
        assert len(res) == 4
    finally:
        fab.shutdown()


def test_fabric_no_replica_code(tiny, fns):
    cfg, model, params = tiny
    fab = Fabric(model, params, replicas=1, n_slots=1, max_seq=32, fns=fns)
    try:
        fab.kill_replica(0)
        out = fab.submit(Request(rid=0, prompt=(1, 2), max_new_tokens=2))
        assert out == {"rid": 0, "admitted": False, "code": "NO_REPLICA"}
    finally:
        fab.shutdown()


def test_fabric_replica_death_rehomes_without_token_loss(tiny, fns, probe):
    cfg, model, params = tiny
    master = Fleet(2, seed=5)
    fab = Fabric(model, params, replicas=2, fleet=master, n_slots=2,
                 max_seq=32, seed=0, fns=fns, probe=probe)
    try:
        queue = _queue(cfg, 8, gen=(4, 8))
        for r in queue:
            assert fab.submit(r)["admitted"]
        on_zero = [rid for rid, wid in fab._home.items() if wid == 0]
        assert on_zero  # the victim holds real work
        fab.pump()  # some requests mid-generation
        fab.kill_replica(0)
        res = fab.run()
        # nothing lost: every request (including re-homed mid-flight
        # ones) finishes with its FULL token budget on the survivor
        assert set(res) == {r.rid for r in queue}
        for r in queue:
            assert len(res[r.rid]["tokens"]) == r.max_new_tokens, r.rid
        rep = fab.fabric_report()
        assert rep["readmitted"] > 0
        assert rep["per_replica"][0]["state"] == "dead"
    finally:
        fab.shutdown()


def test_fabric_replica_death_rehomes_two_lane_keys_in_order(tiny, fns):
    """A dead replica's engine queue holds two lane keys, interleaved: the
    survivor receives its requests in submission order across the keys,
    then the in-flight ones, and every one finishes in full."""
    cfg, model, params = tiny
    fab = Fabric(model, params, replicas=2, n_slots=1, max_seq=32,
                 seed=0, fns=fns)
    try:
        queue = _queue(cfg, 8, gen=(3, 6))  # exact, log_mult, exact, ...
        victim, survivor = fab.workers
        for r in queue:
            assert victim.enqueue(r)
        victim.run_once()  # one slot per key: two admitted, six queued
        eng = victim.engine
        queued = [req.rid for req, _ in eng.pending]
        assert queued == [r.rid for r in queue[2:]]
        assert {approx.active for _, approx in eng.pending} == {False, True}
        in_flight = [st.req.rid for lane in eng.lanes.values()
                     for st in lane.slots if st is not None]
        assert sorted(in_flight) == [0, 1]

        rehomed = []
        enqueue = survivor.enqueue

        def record(req):
            rehomed.append(req.rid)
            return enqueue(req)

        survivor.enqueue = record
        fab.kill_replica(0)
        fab.pump()
        assert rehomed == queued + in_flight
        assert not eng.pending and len(eng.pending) == 0
        res = fab.run()
        assert set(res) == {r.rid for r in queue}
        for r in queue:
            assert len(res[r.rid]["tokens"]) == r.max_new_tokens, r.rid
    finally:
        fab.shutdown()


def test_fabric_recal_push_applies_at_step_boundary(tiny, fns, probe):
    cfg, model, params = tiny
    fleet = Fleet(1, seed=2)
    engine = Engine(model, params, n_slots=2, max_seq=32, fleet=fleet,
                    external_recal=True, fns=fns, probe=probe)
    prompt = tuple(int(x) for x in
                   np.random.default_rng(0).integers(0, cfg.vocab_size, 4))
    engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=6,
                          backend="log_mult"))
    engine.step()
    lane = next(l for l in engine.lanes.values() if l.chip is not None)
    old_calib = lane.calib
    lane.awaiting_recal = True
    marker = jax.tree_util.tree_map(lambda x: x, old_calib)
    engine.push_calib(lane.key, marker, probe_loss=1.23, corrected_loss=0.9)
    # queued, not applied: the swap waits for the next step boundary
    assert lane.calib is old_calib and lane.awaiting_recal
    engine.step()
    assert lane.calib is marker and not lane.awaiting_recal
    assert engine.recal_pushes == 1
    assert lane.probe_losses[-1][1] == 1.23
    assert lane.corrected_losses[-1][1] == 0.9
    # the refreshed stats are parked in the fleet's per-chip store
    assert fleet.calib_for(lane.chip_id) is marker
    # a push for an evicted lane is dropped, not crashed
    engine.push_calib((lane.approx, 99), marker)
    assert engine.apply_pushes() == 0


def test_fabric_async_recal_pushes_and_zero_retraces(tiny, fns, probe):
    cfg, model, params = tiny
    master = Fleet(2, seed=3, variation=VariationModel(scale=1.5))
    drift = DriftModel(gain_walk_std=0.6, offset_walk_std=0.3)
    queue = _queue(cfg, 8, backends=("log_mult", "approx_mult"), gen=(4, 6))
    kw = dict(replicas=2, fleet=master, drift=drift, n_slots=2, max_seq=32,
              recalibrate_every=3, seed=0, probe=probe)
    warm = Fabric(model, params, fns=fns, **kw)
    warm.run(queue)
    warm.shutdown()
    t0 = warm.fns.stats()["traces"]
    fab = Fabric(model, params, fns=warm.fns, **kw)
    try:
        res = fab.run(queue)
        assert len(res) == len(queue)
        rep = fab.fabric_report()
        # drift fired, the service refitted off the hot path, and the
        # pushed coefficient swaps recompiled nothing, fabric-wide
        assert rep["recal_pushes"] > 0
        assert rep["recal_service"]["fits"] > 0
        assert warm.fns.stats()["traces"] == t0
        assert warm.fns.stats()["retraces"] == 0
    finally:
        fab.shutdown()


def test_fabric_slo_drain_and_retire(tiny, fns, probe):
    cfg, model, params = tiny
    master = Fleet(2, seed=4)
    # absolute-loss SLO set below the model's probe loss: every probe
    # breaches, so after K=2 consecutive observations the router drains
    # the replica (fleet engines have no demote rung)
    policy = RouterPolicy(slo_loss=0.05, slo_patience=2, demote_sites=None)
    fab = Fabric(model, params, replicas=2, fleet=master, n_slots=2,
                 max_seq=32, policy=policy, recalibrate_every=2, seed=0,
                 drift=DriftModel(gain_walk_std=0.5), fns=fns, probe=probe)
    try:
        res = fab.run(_queue(cfg, 12, backends=("log_mult",), gen=(4, 8)))
        assert len(res) == 12  # draining replicas serve out their work
        rep = fab.fabric_report()
        states = [r["state"] for r in rep["per_replica"]]
        # one replica retired; the survivor is protected by the
        # last-live-replica guard however sick it probes
        assert states.count("retired") == 1
        assert states.count("live") == 1
        assert rep["retired"] == 1
        entry = rep["retirements"][0]
        assert entry["reason"] == "slo"
        retired_wid = states.index("retired")
        assert master.is_retired(fab.workers[retired_wid].master_ids[0])
        # the engine-level fleet report carries the retired flag too
        lanes = [l for l in rep["fleet"] if l["wid"] == retired_wid]
        assert lanes and all(l["retired"] for l in lanes)
        # the refusal to retire the last replica is on the action log
        assert any(a["action"] == "retire_refused_last_replica"
                   for a in rep["router"]["actions"])
    finally:
        fab.shutdown()


def test_fleet_report_drift_age_agrees_across_lanes(tiny, fns, probe):
    cfg, model, params = tiny
    # TWO lanes (log_mult + approx_mult) bound to ONE chip: their
    # fleet_report drift ages must agree — age is the chip's
    # fleet-global token counter, not a lane-local count
    fleet = Fleet(1, seed=6)
    engine = Engine(model, params, n_slots=2, max_seq=32, fleet=fleet,
                    drift=DriftModel(gain_walk_std=0.2), fns=fns,
                    probe=probe)
    engine.run(_queue(cfg, 6, backends=("log_mult", "approx_mult"),
                      gen=(4, 6)))
    report = engine.fleet_report()
    assert len(report) == 2
    ages = {row["age_tokens"] for row in report}
    assert len(ages) == 1, f"lanes on one chip disagree on age: {report}"
    assert ages == {fleet.tokens_served(0)}
    assert next(iter(ages)) > 0
    # lane-local profile copies sync to the shared counter lazily (each
    # catches up when it next serves), so they trail it but never pass it
    for lane in engine.lanes.values():
        if lane.chip is not None:
            assert float(np.asarray(lane.chip["age"])) <= fleet.tokens_served(0)


def test_fabric_smoke_report_shape(tiny, fns, probe):
    cfg, model, params = tiny
    master = Fleet(2, seed=7)
    fab = Fabric(model, params, replicas=2, fleet=master, n_slots=2,
                 max_seq=32, seed=0, fns=fns, probe=probe)
    try:
        queue = _queue(cfg, 6, backends=("exact", "log_mult"))
        res = fab.run(queue)
        assert {len(r["tokens"]) for r in res.values()} == \
               {r.max_new_tokens for r in queue}
        rep = fab.fabric_report()
        assert rep["completed"] == 6
        assert rep["agg_tok_s_busy"] > 0 and rep["max_busy_s"] > 0
        assert "busy" in rep["tok_s_provenance"]
        assert len(rep["per_replica"]) == 2
        assert rep["compile_stats"]["retraces"] == 0
        assert rep["router"]["policy"] == "health"
    finally:
        fab.shutdown()


def test_fabric_threaded_mode_serves(tiny, fns):
    cfg, model, params = tiny
    fab = Fabric(model, params, replicas=2, n_slots=2, max_seq=32,
                 threads=True, seed=0, fns=fns)
    try:
        res = fab.run(_queue(cfg, 5, backends=("exact",), gen=(3, 4)))
        assert len(res) == 5
        assert all(len(r["tokens"]) > 0 for r in res.values())
    finally:
        fab.shutdown()
