"""A run whose timed path is broken underneath comes out not correct
under the cell's own limits (``bench/limits/<cell>.json``), and so does
each control put in the program's place; the same run unbroken comes
out correct.  The faults are planted in the program's classes by
``tiny.planted``; the cells run at tiny sizes on the CPU."""
import pytest

import tiny


def cell_limits(cell):
    data = tiny.load(tiny.BENCH / "limits" / f"{cell}.json")
    return {k: float(v) for k, v in data["limits"].items()}


SERVE_LIMITS = cell_limits(tiny.SERVE_CELL)
TRAIN_LIMITS = cell_limits(tiny.TRAIN_CELL)


def train_parts():
    """The training cell at a size the CPU runs in seconds, wide enough
    that the stochastic streams' noise stays inside the cell's limits
    (at the default tiny widths it does not)."""
    return tiny.train_parts(hidden=256, ff=512, vocab=4096, layers=1, seq=128)


@pytest.mark.parametrize("fault", [None, "state-unchanged"], ids=["sound", "state-unchanged"])
def test_train_faults(tmp_path, fault):
    res, _ = tiny.execute(train_parts(), seed=5, seconds=1.0, tmp_path=tmp_path,
                          limits=TRAIN_LIMITS, fault=fault)
    assert res["correct"] is (fault is None)
    assert list(res)[-1] == "checks"


def test_train_control_fails(tmp_path):
    """The reference at half the stream length in the program's place is
    not correct; each control's verdict is the cell's own judge's."""
    res, _ = tiny.execute(train_parts(), seed=6, seconds=1.0, tmp_path=tmp_path,
                          limits=TRAIN_LIMITS, control=True)
    assert res["control"]["half_streams"]["correct"] is False


@pytest.mark.parametrize("fault", [None, "token-altered", "cache-unchanged"],
                         ids=["sound", "token-altered", "cache-unchanged"])
def test_serve_faults(tmp_path, fault):
    res, _ = tiny.execute(tiny.serve_parts(), seed=5, seconds=2.0,
                          tmp_path=tmp_path, limits=SERVE_LIMITS, fault=fault)
    assert res["correct"] is (fault is None)
    assert list(res)[-1] == "checks"


def test_serve_control_fails(tmp_path):
    """The reference in float8 in the program's place is not correct."""
    res, _ = tiny.execute(tiny.serve_parts(), seed=6, seconds=2.0, tmp_path=tmp_path,
                          limits=SERVE_LIMITS, control=True)
    assert res["control"]["fp8"]["correct"] is False
