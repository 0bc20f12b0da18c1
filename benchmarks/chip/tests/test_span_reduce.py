"""The program's spans against the device trace, on hand-made traces:
the clock tie, the idle split by overlap, and the readers' arithmetic."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

import span_reduce as sr
import trace_reduce as tr

OFFSET = 40_000_000 - 37_000_000_000_000     # trace clock - program clock


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def trace(ops, host, window=(0, 1000)):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("op", s, e - s) for s, e in ops]),
        NS(name="XLA Modules", events=[])])
    events = [ev("bench.window", window[0], window[1] - window[0])]
    events += [ev(n, s, e - s) for n, s, e in host]
    return tr.from_planes([device, NS(name="/host:CPU", lines=[
        NS(name="python", events=events)])])


def span(id_, name, t0, t1, parent=-1, depth=0, **kw):
    """A finished span as the recorder writes it, times on the trace's
    clock shifted to the program's."""
    return {"id": id_, "parent": parent, "name": name, "depth": depth,
            "t0_ns": t0 - OFFSET, "t1_ns": t1 - OFFSET,
            "wall_ms": (t1 - t0) * 1e-6, "sync_ms": 0.0, **kw}


def serve_call(base, id0, t0):
    """`serve` [t0, t0+300) > classify [t0+10, t0+50), merge [t0+150, end)."""
    return [span(id0 + 1, "classify", t0 + 10, t0 + 50, id0, 1),
            span(id0 + 2, "merge", t0 + 150, t0 + 300, id0, 1),
            span(id0, "serve", t0, t0 + 300, n=base)]


def test_gap_across_a_span_boundary_splits_by_overlap():
    t = trace(ops=[(100, 200), (600, 700)], host=[("bench.serve", 245, 560)])
    spans = serve_call(2, 0, 250)
    idle = sr.split_idle(t, spans, OFFSET)
    # the gap [200, 600) has its midpoint in `merge`; by overlap it is
    # 50 before the call, 10 + 100 serve, 40 classify, 150 merge, 50 after
    assert idle["merge"] == pytest.approx(150e-9)
    assert idle["classify"] == pytest.approx(40e-9)
    assert idle["serve"] == pytest.approx(110e-9)
    # [0, 100) and [700, 1000) are idle too, with no span open
    assert idle[sr.UNATTRIBUTED] == pytest.approx((100 + 50 + 50 + 300)
                                                  * 1e-9)


def test_idle_by_span_adds_up_to_the_idle_share():
    rng = np.random.default_rng(0)
    ops = sorted((int(s), int(s) + int(d)) for s, d in zip(
        rng.integers(0, 100_000, 300), rng.integers(1, 400, 300)))
    spans, host = [], []
    for k, t0 in enumerate(range(500, 99_000, 1_000)):
        spans += serve_call(4, 3 * k, t0)
        host.append(("bench.serve", t0 - 5, t0 + 320))
    t = trace(ops, host, window=(0, 100_000))
    idle = sr.split_idle(t, spans, OFFSET)
    assert sum(idle.values()) == pytest.approx(
        tr.idle_pct(t) / 100 * t.window_s, rel=1e-9)
    assert set(idle) == {"serve", "classify", "merge", sr.UNATTRIBUTED}


def test_known_offset_with_jitter_is_recovered():
    rng = np.random.default_rng(1)
    jitter = 20_000                               # ns
    starts = np.cumsum(rng.integers(2_000_000, 60_000_000, 400)) + 10**9
    host = [("bench.serve", int(s), int(s) + 1_500_000) for s in starts]
    spans = []
    for k, s in enumerate(starts):
        t0 = int(s) + int(rng.integers(0, jitter))
        spans += serve_call(3, 3 * k, t0)
    offset = sr.tie_clock(host, spans, (0, 10**13))
    assert offset is not None
    assert abs(offset - OFFSET) <= jitter


@pytest.mark.parametrize("jitter_ms", [0, 40])
def test_refit_window_of_long_annotations_ties(jitter_ms):
    """Each refit annotation holds several root spans back to back, and
    set-up ran the same refits before the window: a shift by a root or by
    a refit keeps every annotation holding one. The starts and the roots
    left outside every annotation tell the true offset; the first root
    of each annotation is its pair."""
    rng = np.random.default_rng(2)
    ms = 1_000_000
    host, spans, k = [], [], 0

    def root(name, t0, t1):
        nonlocal k
        spans.append(span(k, name, t0, t1))
        k += 1
    t = 10**9
    for i in range(-2, 10):                       # two cycles of set-up
        t += 6_200 * ms + int(rng.integers(0, jitter_ms + 1)) * ms
        if i == 0:
            t += 900 * ms                         # the tracer starts
            lo = t - 100 * ms
        d = [int(x) for x in rng.integers(5_000, 40_000, 2)]
        root("prune", t + d[0], t + 13 * ms)
        root("solve", t + 13 * ms, t + 1_000 * ms)
        root("tiering", t + 1_000 * ms, t + 2_200 * ms)
        root("classify_log", t + 2_200 * ms, t + 3_399 * ms)
        root("prepare", t + 3_400 * ms + d[1], t + 5_900 * ms)
        root("rollout", t + 5_900 * ms, t + 6_000 * ms)
        root("serve", t + 6_000 * ms, t + 6_190 * ms)
        if i >= 0:                                # the window's refits
            host += [("bench.refit.solve", t, t + 3_400 * ms),
                     ("bench.refit.swap", t + 3_400 * ms, t + 6_195 * ms)]
    offset = sr.tie_clock(host, spans, (lo, t + 6_300 * ms))
    assert offset is not None and abs(offset - OFFSET) <= 40_000


def test_mismatched_pairs_give_no_tie(capsys):
    host = [("bench.serve", s, s + 400) for s in (1_000, 3_000, 5_000)]
    spans = serve_call(1, 0, 1_010) + serve_call(1, 3, 5_010)
    window = (0, 10_000)
    assert sr.tie_clock(host, spans, window) is None   # none in the second
    assert "no tie" in capsys.readouterr().err
    assert sr.tie_clock(host, [], window) is None
    assert sr.tie_clock([], spans, window) is None


def test_innermost_clips_a_child_to_its_parent():
    spans = [span(0, "a", 0, 100), span(1, "b", 50, 150, 0, 1)]
    assert sr.innermost(spans, OFFSET, 0, 1000) == [
        (0, 50, "a"), (50, 100, "b")]


def run_of(spans, t=None, refits=()):
    return NS(spans=spans, trace=t, refits=list(refits))


def test_serve_readers():
    spans = serve_call(4, 0, 250)
    spans[0].update(sync_ms=0.0)
    match = span(9, "t2_match", 60, 140, 0, 1, sync_ms=4e-5,
                 fetch_ms=1e-5, fetch_bytes=64)
    serve = spans[-1]
    serve.update(cpu_ms=1e-4, minflt=0, majflt=0, nivcsw=0)
    run = run_of(spans[:-1] + [match, serve])
    # serve 300 ns, children 40 + 150 + 80: 30 ns of its own, 4 queries
    assert sr.serve_ms_per_query(run, sr.self_ms) == pytest.approx(
        30e-6 / 4)
    assert sr.serve_ms_per_query(run, sr.match_ms("sync_ms")) == \
        pytest.approx(1e-5)
    assert sr.serve_ms_per_query(run, sr.match_ms("fetch_ms")) == \
        pytest.approx(2.5e-6)
    # wall 3e-4 ms less cpu 1e-4 less sync and fetch 5e-5
    assert sr.serve_ms_per_query(run, sr.stall_ms) == pytest.approx(
        1.5e-4 / 4)
    del serve["cpu_ms"]                           # an older recorder
    assert sr.serve_ms_per_query(run, sr.stall_ms) is None
    assert sr.serve_ms_per_query(run_of([]), sr.self_ms) is None
    old = [{k: v for k, v in s.items() if k != "t0_ns"} for s in spans]
    assert sr.serve_ms_per_query(run_of(old), sr.self_ms) is None


def test_refit_readers_read_the_last_refits():
    def refit(base, k):
        step = span(base + 2, "greedy.step", k + 30, k + 70, base + 1, 1,
                    sync_ms=1e-5)
        return [span(base, "prune", k, k + 20), step,
                span(base + 1, "solve", k + 25, k + 80),
                span(base + 3, "tiering", k + 80, k + 90),
                span(base + 4, "build", k + 95, k + 99, base + 5, 1),
                span(base + 5, "prepare", k + 92, k + 100)]
    spans = refit(0, 0) + refit(10, 1_000) + refit(20, 2_000)
    run = run_of(spans, refits=[object(), object()])   # the window's two
    assert [s["t0_ns"] + OFFSET for s in sr.refit_spans(run)
            if s["name"] == "prune"] == [1_000, 2_000]
    assert sr.per_refit_ms(run, "prune") == pytest.approx(20e-6)
    assert sr.per_refit_ms(run, "tiering", "classify_log") == \
        pytest.approx(10e-6)
    assert sr.per_refit_ms(run, "build") == pytest.approx(4e-6)
    assert sr.greedy_step_ms(run, lambda s, k: s["sync_ms"]) == \
        pytest.approx(1e-5)
    assert sr.per_refit_ms(run_of(spans, refits=[0] * 4), "prune") is None


def test_analyse_books_idle_by_span(capsys):
    t = trace(ops=[(100, 200), (600, 700)], host=[("bench.serve", 245, 560)])
    spans = serve_call(2, 0, 250)
    spans[-1].update(cpu_ms=1e-4, minflt=1, majflt=0, nivcsw=2)
    run = run_of(spans, t)
    assert sr.idle_pct(run, "merge") == pytest.approx(15.0)
    assert sr.idle_pct(run, sr.UNATTRIBUTED) == pytest.approx(50.0)
    err = capsys.readouterr().err
    assert "clock tie: 1 pairs" in err and "serve stall" in err
    assert "merge" in err
    from repro import obs
    obs.reset()                         # no window spans, an empty ring
    assert sr.analyse(run_of([], t)) is None
    assert sr.idle_pct(run_of([], None), "merge") is None
