"""Shared arithmetic of the per-layer metric readers in `layer_metrics/`.

Each reader returns None where its run holds nothing to read, and the
metric is then left out of the result line.
"""
from __future__ import annotations

import trace_reduce


def span_ms_per_query(run, name: str) -> float | None:
    """Self time of the program's `name` spans in the window (leaf spans:
    their wall time) over the queries they handled (their `n`)."""
    spans = [s for s in run.spans if s["name"] == name]
    n = sum(s.get("n", 0) for s in spans)
    if not n:
        return None
    return sum(s["wall_ms"] for s in spans) / n


def idle_pct(run) -> float | None:
    return None if run.trace is None else trace_reduce.idle_pct(run.trace)


def program_roofline_pct(run, name_part: str, least_bytes: float,
                         ) -> float | None:
    """Least HBM time of `least_bytes` over the device time of the
    programs named `name_part`, in percent."""
    if run.trace is None:
        return None
    seconds, _ = trace_reduce.program_s(run.trace, name_part)
    if seconds <= 0 or least_bytes <= 0:
        return None
    return 100.0 * least_bytes / run.peaks["hbm_bytes_per_s"] / seconds


def refit_mean_ms(run, field: str) -> float | None:
    if not run.refits:
        return None
    return 1e3 * sum(getattr(r, field) for r in run.refits) / len(run.refits)
