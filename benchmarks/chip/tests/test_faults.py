"""The harness end to end at a CPU size, with the chip check skipped: a
sound run is `correct`, the control is not, and a run with the timed path
broken underneath comes out not `correct` for each fault a cell can have.
(The exchange between chips is no fault here: every cell runs on one
chip.)"""
import json
import os
import time

import numpy as np
import pytest

import bench

SEED = 2 ** 33 + 5          # larger than 32 signed bits hold
SECONDS = 1.0


def spec_at(size):
    spec = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    for c in spec["configs"]:
        c["file"] = f"benchmarks/chip/tests/{size}.json"
    return spec


def run(name, control=False, size="tiny"):
    return bench.run_cell(name, SEED, SECONDS, False,
                          t_start=time.perf_counter(), spec=spec_at(size),
                          control=control, compile_cache=False)


def failed(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])


@pytest.mark.parametrize("name", ["passage-q1.zipf-r80",
                                  "passage-q1.refit-rotate"])
def test_sound_run_is_correct_and_control_is_not(name):
    # at the tiny size the bfloat16 greedy can agree with float64 throughout
    result = run(name, size="small")
    assert result["correct"], failed(result)
    assert list(result)[-1] == "checks"
    json.dumps(result)
    control = run(name, control=True, size="small")
    assert not control["correct"]
    assert failed(control)


def altered_answer(monkeypatch):
    from repro.serve import matching
    real = matching.match_batch

    def altered(postings, tokens):
        out = real(postings, tokens)
        return out.at[:, 0].set(out[:, 0] ^ 1)
    monkeypatch.setattr(matching, "match_batch", altered)


def half_batch(monkeypatch):
    from repro.cluster import router
    real = router.TieredCluster.serve

    def half(self, queries):
        keep = (len(queries) + 1) // 2
        out = real(self, queries[:keep])
        return out + [np.zeros(0, np.int64)] * (len(queries) - keep)
    monkeypatch.setattr(router.TieredCluster, "serve", half)


def state_unchanged(monkeypatch):
    from repro.api import pipeline
    monkeypatch.setattr(pipeline.TieringPipeline, "refit",
                        lambda self, weights, **kw: self)


def prune_keeps_all(monkeypatch):
    import repro.stream as stream
    real = stream.prune_state
    monkeypatch.setattr(stream, "prune_state", lambda problem, state, **kw:
                        real(problem, state, weights=kw["weights"],
                             min_unique_mass=0.0))


@pytest.mark.parametrize("name,fault", [
    ("passage-q1.zipf-r80", altered_answer),
    ("passage-q1.zipf-r80", half_batch),
    ("passage-q1.refit-rotate", altered_answer),
    ("passage-q1.refit-rotate", half_batch),
    ("passage-q1.refit-rotate", state_unchanged),
    ("passage-q1.refit-rotate", prune_keeps_all),
])
def test_fault_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    result = run(name)
    assert not result["correct"]
    assert failed(result)
