"""Closed loop of refits over one cycle of drift windows.

Each refit takes the steps of the program's re-tiering controller: prune
the previous selection under the new weights (`stream.prune_state`), warm
`TieringPipeline.refit` from what survives, fall back to a cold refit when
the warm tiering covers less of the new traffic than the offline one,
build the new Tier-1 off the request path (`prepare_tiering`), swap it in
(`swap_tiering(immediate=True)`) and serve one probe batch at the new
generation. A cycle starts again from the offline solve, so every cycle
repeats the same refits: set-up runs one cycle, which compiles every shape
the window uses, and the window runs whole cycles.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Refit:
    position: int                  # index within the cycle
    kind: str                      # "warm" | "cold"
    kept: np.ndarray               # clause ids the prune kept
    warm_selected: np.ndarray      # bool [C] the warm refit's selection
    prefix: np.ndarray             # clause ids the final solve resumed from
    selected: np.ndarray           # bool [C] the program's selection
    order: list                    # clause ids the solve added, in order
    tier1_docs: np.ndarray | None  # bool [n_docs], first cycle only
    probe_answers: list            # ids per probe query
    probe_tier1: int               # probe queries Tier 1 served
    solve_s: float
    swap_s: float


class Loop:
    def __init__(self, pipe, fleet, steps, prune_below: float, annotate):
        self.pipe, self.fleet, self.steps = pipe, fleet, steps
        self.prune_below = prune_below
        self.annotate = annotate
        self.base = pipe.result
        self.base_selected = np.asarray(pipe.result.selected, bool)
        self.base_elig = pipe.tiering().classify_queries(pipe.log.query_bits)
        self.queries = pipe.log.queries

    @staticmethod
    def coverage(eligible: np.ndarray, weights: np.ndarray) -> float:
        """Traffic share a tiering sends to Tier 1."""
        return float(np.asarray(weights)[eligible].sum())

    def refit(self, position: int, state, keep_docs: bool) -> tuple:
        from repro.stream import prune_state
        step = self.steps[position]
        pipe, fleet = self.pipe, self.fleet
        t0 = time.perf_counter()
        with self.annotate("bench.refit.solve"):
            state, kept, _ = prune_state(pipe.problem, state,
                                         weights=step.weights,
                                         min_unique_mass=self.prune_below)
            pipe.refit(step.weights, state=state)
            kind, prefix = "warm", kept
            warm_selected = np.asarray(pipe.result.selected, bool)
            tiering = pipe.tiering()
            eligible = tiering.classify_queries(pipe.log.query_bits)
            if self.coverage(eligible, step.weights) + 1e-9 < \
                    self.coverage(self.base_elig, step.weights):
                pipe.refit(step.weights, state=None)
                kind, prefix = "cold", np.zeros(0, np.int64)
                tiering = pipe.tiering()
        t1 = time.perf_counter()
        with self.annotate("bench.refit.swap"):
            buf = fleet.prepare_tiering(tiering)
            fleet.swap_tiering(buf, immediate=True)
            answers = fleet.serve([self.queries[i] for i in step.probe_ids])
        t2 = time.perf_counter()
        rec = Refit(position=position, kind=kind,
                    kept=np.asarray(kept, np.int64),
                    warm_selected=warm_selected,
                    prefix=np.asarray(prefix, np.int64),
                    selected=np.asarray(pipe.result.selected, bool),
                    order=[int(j) for j in pipe.result.order],
                    tier1_docs=np.asarray(tiering.tier1_docs, bool)
                    if keep_docs else None,
                    probe_answers=answers,
                    probe_tier1=fleet.trace[-1].n_tier1,
                    solve_s=t1 - t0, swap_s=t2 - t1)
        return rec, pipe.result.state

    def cycle(self, keep_docs: bool) -> list[Refit]:
        state, out = self.base.state, []
        for pos in range(len(self.steps)):
            rec, state = self.refit(pos, state, keep_docs)
            out.append(rec)
        return out
