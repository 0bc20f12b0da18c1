"""The reduction from trace to metrics, on a hand-made trace and on a
small trace recorded on a TPU v5e (`data/`)."""
import glob
import os
from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion", 100, 50), ev("copy", 120, 60),
                                   ev("gather", 400, 100),
                                   ev("late", 950, 100)]),
        NS(name="XLA Modules", events=[ev("jit_match_batch(1)", 100, 80),
                                       ev("jit_other", 400, 100),
                                       ev("jit_match_batch(2)", 950, 100)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000), ev("bench.serve", 50, 300),
        ev("bench.wait", 600, 300), ev("PjitFunction(f)", 60, 10)])])
    return [device, host]


def test_hand_made_trace():
    t = tr.from_planes(planes())
    assert t.window == (0, 1000) and t.window_s == pytest.approx(1e-6)
    # ops [100,180) and [400,500), and [950,1000) clipped at the window
    assert tr.busy_s(t) == pytest.approx(230e-9)
    assert tr.idle_pct(t) == pytest.approx(77.0)
    seconds, n = tr.program_s(t, "match_batch")
    assert n == 2 and seconds == pytest.approx(130e-9)
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["gather", pytest.approx(100e-9)]
    gaps = dict(b["idle_gaps"])
    # idle [0,100) at 50 and [180,400) at 290 fall in bench.serve,
    # [500,950) at 725 in bench.wait
    assert gaps["bench.serve"] == pytest.approx(320e-9)
    assert gaps["bench.wait"] == pytest.approx(450e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_plane_reads_nothing():
    t = tr.from_planes(planes()[1:])
    assert tr.idle_pct(t) is None
    assert tr.program_s(t, "match_batch") == (0.0, 0)


def test_missing_window_is_an_error():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.serve", 0, 10)])])
    with pytest.raises(ValueError):
        tr.from_planes([host])


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    DATA, "*.xplane.pb"))))
def test_recorded_tpu_trace(path):
    t = tr.load(path)
    assert t.ops and t.modules
    assert 0.0 < tr.busy_s(t) < t.window_s
    assert 0.0 < tr.idle_pct(t) < 100.0
    seconds, n = tr.program_s(t, "match_batch")
    assert n > 0 and 0.0 < seconds < t.window_s
    b = tr.breakdown(t)
    assert b["device_ops"] and b["idle_gaps"]
