"""Cost-aware greedy for SCSK (paper eq. 13) — dense recompute-all variant.

Each step evaluates f(j|X) and g(j|X) for every candidate (two fused kernel
calls) and adds argmax_{feasible} f(j|X)/g(j|X). This is the semantics of
record: Lazy Greedy (Alg. 1) and Opt/Pes Greedy (Alg. 2) must select the same
sequence (up to exact ties), which the tests assert.

Registered as "greedy" (`repro.api`). Warm-startable: pass the `state` of a
previous `SolverResult` to resume — with `stop_policy="truncate"` the
selection path is budget-independent, so `solve_sweep` resumes across budgets
instead of re-solving from scratch (paper Fig. 3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.config import SolveConfig
from repro.core.constraint import as_constraint, resolve_constraint
from repro.core.problem import SCSKProblem, SolverResult
from repro.core.registry import register_solver
from repro.core.state import SolverState
from repro.core.trace import Trace

BIG = 1e12   # ratio stand-in for "free" clauses (g-gain == 0, f-gain > 0)


def ratio_of(fg: jax.Array, gg: jax.Array) -> jax.Array:
    return jnp.where(gg <= 0.0, fg * BIG, fg / jnp.maximum(gg, 1e-30))


@functools.partial(jax.jit, static_argnames=("cost_aware", "truncate"))
def _greedy_step(problem: SCSKProblem, state: SolverState, constraint, *,
                 cost_aware: bool = True, truncate: bool = False):
    fg = problem.f_gains(state.covered_q)
    gg, gg_part = constraint.gains(problem, state.covered_d)
    used = constraint.used(problem, state)
    candidates = (~state.selected) & (fg > 0.0)
    feasible = candidates & constraint.feasible(used, gg_part)
    score = ratio_of(fg, gg) if cost_aware else fg
    score = jnp.where(candidates if truncate else feasible, score, -jnp.inf)
    j = jnp.argmax(score)
    stop = ~feasible[j]
    applied = problem.apply(state, j)
    state = jax.tree_util.tree_map(
        lambda cur, new: jnp.where(stop, cur, new), state, applied)
    f_val = problem.f_value(state.covered_q)
    return state, f_val, j, stop


def greedy_step(problem: SCSKProblem, state: SolverState, budget, *,
                cost_aware: bool = True, truncate: bool = False):
    """One greedy selection over a SolverState.

    `budget` is a scalar knapsack budget or any `KnapsackConstraint` (a
    `PartitionedBudget` masks candidates that overflow ANY per-shard cap).
    Returns (state, f_val, j, stop). `truncate=False` masks the score to
    feasible candidates ("exhaust": classic greedy); `truncate=True` ranks
    ALL unselected candidates and stops at the first infeasible argmax, which
    makes the selection path budget-independent (warm-start sweeps).
    """
    return _greedy_step(problem, state, as_constraint(budget),
                        cost_aware=cost_aware, truncate=truncate)


@register_solver("greedy", supports_state=True, supports_truncate=True,
                 supports_partition=True,
                 description="dense cost-ratio greedy (paper eq. 13)")
def solve_greedy(problem: SCSKProblem, config: SolveConfig,
                 state: SolverState | None = None) -> SolverResult:
    cost_aware = bool(config.opt("cost_aware", True))
    state = problem.init_state() if state is None else state
    trace = Trace(config, f0=float(problem.f_value(state.covered_q)),
                  g0=float(state.g_used))
    constraint = resolve_constraint(problem, config)
    truncate = config.stop_policy == "truncate"
    c = problem.n_clauses

    order: list[int] = []
    steps = config.max_steps or c
    for _ in range(steps):
        with obs.span("greedy.step") as sp:
            state, f_val, j, stop = _greedy_step(
                problem, state, constraint, cost_aware=cost_aware,
                truncate=truncate)
            trace.add_evals(2 * c)
            if bool(sp.sync(stop)):
                break
            order.append(int(j))
            trace.on_select(float(f_val), float(state.g_used))
            if trace.should_stop():
                break
    name = "greedy" if cost_aware else "agnostic-dense"
    return trace.result(name, problem, state, order)


def greedy(problem: SCSKProblem, budget: float, *, cost_aware: bool = True,
           max_steps: int | None = None, record_every: int = 1,
           time_limit: float | None = None) -> SolverResult:
    """Legacy keyword entrypoint; prefer `repro.api.solve`."""
    return solve_greedy(problem, SolveConfig(
        budget=budget, solver="greedy", max_steps=max_steps,
        record_every=record_every, time_limit=time_limit,
        options={"cost_aware": cost_aware}))
