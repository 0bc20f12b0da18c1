"""Sliding-window log accumulation + warm-start state hygiene.

`LogAccumulator` maintains exponentially-decayed empirical query weights over
the unique-query universe — the online analogue of the offline QueryLog's
`train_weights`. Its `weights()` feed `SCSKProblem.with_weights` (bitset
reuse) and `TieringPipeline.refit`.

`prune_state` is the other half of a cheap re-solve: before warm-starting
from the previous `SolverState`, drop selected clauses whose *unique*
weighted query coverage under the CURRENT distribution has decayed to
nothing. That frees knapsack budget (g) for clauses matching the new traffic
while keeping every still-hot clause — so the warm solve only pays for the
drift delta, not a from-scratch path.

`prune_partitions` scopes a warm re-solve to the doc-space partitions that
actually drifted (shard-aware re-tiering): selected clauses whose document
mass is concentrated in the drifted shards are unfrozen (dropped, freeing
their per-shard budget for the re-solve); every other clause stays in the
warm prefix, so the solver effectively only re-tier the drifted shards.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro import obs
from repro.core import bitset
from repro.core.problem import SCSKProblem
from repro.core.state import SolverState


class LogAccumulator:
    """Exponentially-decayed query counts over the unique-query universe.

    `halflife` is measured in windows: after observing h windows of purely
    new traffic, the old traffic contributes half the mass it did. A prior
    (e.g. the offline log's train_weights, scaled by `prior_strength`
    pseudo-observations) keeps early windows from being all sampling noise.
    """

    def __init__(self, n_queries: int, *, halflife: float = 2.0,
                 prior: np.ndarray | None = None,
                 prior_strength: float = 0.0):
        if halflife <= 0:
            raise ValueError("halflife must be positive (windows)")
        self.n_queries = n_queries
        self.decay = 0.5 ** (1.0 / halflife)
        self.counts = np.zeros(n_queries, np.float64)
        if prior is not None and prior_strength > 0:
            p = np.asarray(prior, np.float64)
            if p.shape != (n_queries,):
                raise ValueError(
                    f"prior must have shape ({n_queries},), got {p.shape}")
            self.counts += prior_strength * p / max(p.sum(), 1e-30)
        self.n_windows = 0

    def observe(self, query_ids: np.ndarray) -> None:
        """Fold one window's sampled query ids into the decayed counts."""
        self.counts *= self.decay
        np.add.at(self.counts, np.asarray(query_ids, np.int64), 1.0)
        self.n_windows += 1

    def weights(self) -> np.ndarray:
        """Normalized decayed empirical distribution, f64 [n_queries]."""
        s = self.counts.sum()
        if s <= 0:
            return np.full(self.n_queries, 1.0 / max(1, self.n_queries))
        return self.counts / s

    def total(self) -> float:
        return float(self.counts.sum())


def check_state_width(problem: SCSKProblem, state: SolverState) -> None:
    """Reject a `SolverState` whose doc bitset width doesn't match `problem`.

    Raised instead of silently zero-padding because the pad would be WRONG:
    after `append_docs` + `with_doc_block`, already-selected clauses may
    match the appended documents, so the only exact post-append state is a
    re-derivation (`problem.state_for`) over the grown incidence.
    """
    wd = int(np.asarray(state.covered_d).shape[0])
    if wd != problem.wd:
        raise ValueError(
            f"stale SolverState: covered_d has {wd} words but the problem "
            f"has wd={problem.wd} (corpus appended since the state was "
            "captured?); re-derive it with "
            "problem.state_for(np.nonzero(state.selected)[0])")
    if int(np.asarray(state.selected).shape[0]) != problem.n_clauses:
        raise ValueError(
            f"stale SolverState: {np.asarray(state.selected).shape[0]} "
            f"selection slots vs {problem.n_clauses} clauses")


@obs.spanned("prune")
def prune_state(problem: SCSKProblem, state: SolverState, *,
                min_unique_mass: float = 0.0,
                weights: np.ndarray | None = None,
                ) -> tuple[SolverState, np.ndarray, np.ndarray]:
    """Drop stale clauses from a SolverState; returns (state, kept, dropped).

    A selected clause is stale when the traffic mass it UNIQUELY covers
    (queries no other selected clause matches) under `weights` (default:
    `problem.query_weights`) is below `min_unique_mass`. Passing `weights`
    directly (length `n_queries`) avoids materializing a reweighted problem
    just for the pruning pass. Unique — not standalone — coverage is the
    right criterion: dropping a clause only loses the queries nothing else
    covers. The pruned state is rebuilt exactly (covered bitsets re-OR'd,
    `g_used` recomputed), so a solver can resume from it as if the kept
    clauses were its own selection prefix.

    Everything stays in the packed-bitset domain: the exactly-once query
    mask is two OR/AND accumulator sweeps over the K selected rows, and the
    per-clause unique mass is one fused `f_gains` (bit-matvec) call with
    that mask folded into the weights — no dense [K, n_queries] incidence
    is ever materialized.

    Width contract (repro.ingest): a state captured BEFORE a corpus append
    is stale — its `covered_d` is narrower than the grown `problem.wd`, and
    zero-padding it would under-count g (old clauses can match appended
    docs). Such a state is rejected with a `ValueError` naming both widths;
    re-derive it at the new width with `rebuild_state(problem, kept)`
    (= `problem.state_for`) before warm-starting a post-append refit.
    """
    check_state_width(problem, state)
    selected = np.asarray(state.selected)
    idx = np.nonzero(selected)[0]
    empty = np.empty(0, np.int64)
    if len(idx) == 0 or min_unique_mass <= 0:
        return state, idx.astype(np.int64), empty

    nq = problem.n_queries
    qrows = np.asarray(problem.clause_query_bits)[idx]            # [K, Wq]
    if weights is None:
        wpad = np.asarray(problem.query_weights, np.float32)
    else:
        weights = np.asarray(weights, np.float32)
        if weights.shape != (nq,):
            raise ValueError(
                f"weights must have shape ({nq},), got {weights.shape}")
        wpad = np.zeros(problem.wq * 32, np.float32)
        wpad[:nq] = weights
    seen_once = np.zeros(problem.wq, np.uint32)
    seen_multi = np.zeros(problem.wq, np.uint32)
    for r in qrows:
        seen_multi |= seen_once & r
        seen_once |= r
    once = seen_once & ~seen_multi            # queries covered exactly once
    # f_gains with covered_q = ~once zeroes every weight outside the mask,
    # so row j of the bit-matvec is exactly clause j's unique weighted mass
    unique_mass = np.asarray(problem.f_gains(
        jnp.asarray(~once), rows=jnp.asarray(qrows), weights=jnp.asarray(wpad)))
    drop = unique_mass < min_unique_mass
    if not drop.any():
        return state, idx.astype(np.int64), empty

    kept = idx[~drop].astype(np.int64)
    return rebuild_state(problem, kept), kept, idx[drop].astype(np.int64)


def rebuild_state(problem: SCSKProblem, kept: np.ndarray) -> SolverState:
    """Exact `SolverState` for a clause subset, as if it were a solve prefix
    (covered bitsets re-OR'd, `g_used` recomputed)."""
    return problem.state_for(kept)


def prune_partitions(problem: SCSKProblem, state: SolverState,
                     bounds: tuple[int, ...], parts,
                     *, scope_frac: float = 0.5,
                     ) -> tuple[SolverState, np.ndarray, np.ndarray]:
    """Unfreeze the clauses living in drifted doc partitions.

    Drops every selected clause whose document mass inside the partitions
    `parts` (indices into the word-aligned `bounds` split) is at least
    `scope_frac` of its total mass; returns (state, kept, dropped) like
    `prune_state`. The kept clauses stay a frozen warm prefix, so a re-solve
    from the returned state only spends budget re-tiering the drifted
    shards (plus whatever slack the caps leave elsewhere). Like
    `prune_state`, a stale-width state (pre-append) raises `ValueError`.
    """
    check_state_width(problem, state)
    selected = np.asarray(state.selected)
    idx = np.nonzero(selected)[0].astype(np.int64)
    parts = sorted(set(int(p) for p in parts))
    empty = np.empty(0, np.int64)
    if len(idx) == 0 or not parts:
        return state, idx, empty
    rows = np.asarray(problem.clause_doc_bits)[idx]              # [K, Wd]
    total = bitset.np_popcount(rows).astype(np.float64)
    in_scope = np.zeros(len(idx), np.float64)
    for k in parts:
        lo, hi = bounds[k], bounds[k + 1]
        in_scope += bitset.np_popcount(rows[:, lo:hi])
    drop = in_scope >= scope_frac * np.maximum(total, 1.0)
    if not drop.any():
        return state, idx, empty
    kept = idx[~drop]
    return rebuild_state(problem, kept), kept, idx[drop]
