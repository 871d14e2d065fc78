"""Traffic is a pure function of the seed, every seed serves the same
lengths, and the open-loop driver times a request from when it was due."""
import numpy as np
import pytest

import tiny
import traffic_gen


def _traffic():
    c = tiny.cell(tiny.SERVE_CELL)
    return tiny.load(tiny.BENCH / "traffic" / f"{c['traffic']}.json")


def test_same_seed_same_requests():
    t = _traffic()
    a = traffic_gen.requests(t, 64000, 2 ** 31 + 12345)
    b = traffic_gen.requests(t, 64000, 2 ** 31 + 12345)
    assert len(a) == len(b) == t["requests"]
    for x, y in zip(a, b):
        assert x["rid"] == y["rid"] and x["max_new_tokens"] == y["max_new_tokens"]
        assert x["due_s"] == y["due_s"]
        np.testing.assert_array_equal(x["prompt"], y["prompt"])


def test_seeds_share_lengths_not_tokens():
    t = _traffic()
    a = traffic_gen.requests(t, 64000, 1)
    b = traffic_gen.requests(t, 64000, 2)
    la = sorted(len(r["prompt"]) for r in a)
    lb = sorted(len(r["prompt"]) for r in b)
    assert la == lb
    assert sorted(r["max_new_tokens"] for r in a) == sorted(r["max_new_tokens"] for r in b)
    assert any(len(x["prompt"]) != len(y["prompt"]) or
               not np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    # stratified: each block of the queue holds the same mix of lengths
    block = int(t.get("block", 64))
    for start in range(0, len(a), block):
        sa = sum(len(r["prompt"]) for r in a[start:start + block])
        sb = sum(len(r["prompt"]) for r in b[start:start + block])
        assert abs(sa - sb) <= 0.02 * sa
    assert min(la) >= t["prompt_len"]["min"] and max(la) <= t["prompt_len"]["max"]


def test_poisson_arrivals_fixed_set():
    t = dict(_traffic(), arrival={"kind": "poisson", "rate_per_s": 8.0})
    a = traffic_gen.requests(t, 100, 5)
    b = traffic_gen.requests(t, 100, 6)
    gaps_a = np.diff([0.0] + [r["due_s"] for r in a])
    gaps_b = np.diff([0.0] + [r["due_s"] for r in b])
    np.testing.assert_allclose(sorted(gaps_a), sorted(gaps_b))
    assert abs(np.mean(gaps_a) - 1 / 8.0) < 0.02


@pytest.mark.parametrize("stall_s", [0.0, 0.6], ids=["steady", "stalled"])
def test_stall_shows_in_ttft(tmp_path, monkeypatch, stall_s):
    """A stall of the serving loop delays every request due meanwhile:
    TTFT, timed from the due time, grows by about the stall."""
    from repro.runtime.engine import Engine

    parts = tiny.serve_parts(arrival={"kind": "poisson", "rate_per_s": 20.0},
                             requests=128, block=16)
    step = Engine.step
    calls = {"n": 0}

    def stalling(self):
        calls["n"] += 1
        if calls["n"] == 30 and stall_s:
            import time
            time.sleep(stall_s)
        return step(self)

    monkeypatch.setattr(Engine, "step", stalling)
    res, ctx = tiny.execute(parts, seed=3, seconds=3.0, tmp_path=tmp_path, limits={})
    ttft = ctx.e2e["ttft_p95_ms"]
    if stall_s:
        assert ttft >= 0.5 * stall_s * 1e3
    else:
        assert ttft < 0.5 * 0.6 * 1e3
