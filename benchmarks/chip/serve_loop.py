"""Open-loop serving window with continuous batching.

Queries are due at the mix's Poisson arrival times. Whenever
`TieredCluster.serve` returns, every query already due, oldest first and
at most `max_batch` of them, goes next. A query's latency runs from its
due time to the return of the call that answered it, so a stall counts
against every query that waited behind it.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class ServeWindow:
    latency_s: np.ndarray          # f64 [n] due time to answer
    done_s: np.ndarray             # f64 [n] answer time, window-relative
    answer_len: np.ndarray         # int64 [n] documents answered, -1 = none
    answers: dict                  # query index -> ids, for the checked ones
    longest: tuple                 # (query index, ids) of the largest answer
    batches: list                  # (first, end, Tier-1 count) per call
    call_s: np.ndarray             # f64 [calls] duration of each serve call
    call_at: np.ndarray            # f64 [calls] its start (perf_counter)
    opened: float                  # window open (perf_counter)
    wall_offset: float             # time.time() - perf_counter()
    elapsed_s: float               # window open to last answer


def warm_shapes(fleet, queries, eligible, max_batch: int) -> int:
    """Serve one batch for every sub-batch size and padded length the
    window can produce, through `serve` only. Returns the calls made."""
    calls = 0
    by_len: dict[int, tuple] = {}
    for q, e in zip(queries, eligible):
        # prefer queries served by Tier 2, so one batch is one sub-batch
        if len(q) not in by_len or (by_len[len(q)][1] and not e):
            by_len[len(q)] = (q, e)
    for length in sorted(by_len):
        q = by_len[length][0]
        for b in range(1, max_batch + 1):
            fleet.serve([q] * b)
            calls += 1
    return calls


def run(fleet, queries, arrivals: np.ndarray, query_ids: np.ndarray,
        max_batch: int, check: set, annotate) -> ServeWindow:
    n = len(arrivals)
    done = np.full(n, np.nan)
    alen = np.full(n, -1, np.int64)
    answers: dict[int, np.ndarray] = {}
    longest: tuple = (-1, np.zeros(0, np.int64))
    batches, call_s, call_at = [], [], []
    i = 0
    wall_offset = time.time() - time.perf_counter()
    t0 = time.perf_counter()
    while i < n:
        now = time.perf_counter() - t0
        if arrivals[i] > now:
            with annotate("bench.wait"):
                time.sleep(arrivals[i] - now)
            continue
        j = int(np.searchsorted(arrivals, now, side="right"))
        j = min(j, i + max_batch)
        batch = [queries[k] for k in query_ids[i:j]]
        t_call = time.perf_counter()
        with annotate("bench.serve"):
            out = fleet.serve(batch)
        done[i:j] = time.perf_counter() - t0
        call_s.append(time.perf_counter() - t_call)
        call_at.append(t_call)
        batches.append((i, j, fleet.trace[-1].n_tier1))
        for k, ids in zip(range(i, j), out):
            alen[k] = len(ids)
            if k in check:
                answers[k] = ids
            if len(ids) > len(longest[1]):
                longest = (k, ids)
        i = j
    elapsed = time.perf_counter() - t0
    return ServeWindow(latency_s=done - arrivals, done_s=done,
                       answer_len=alen, answers=answers, longest=longest,
                       batches=batches, call_s=np.asarray(call_s),
                       call_at=np.asarray(call_at), opened=t0,
                       wall_offset=wall_offset, elapsed_s=elapsed)
