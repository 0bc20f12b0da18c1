"""The program's own spans (`repro.obs`) against the device trace.

The program times its spans on `time.perf_counter_ns()` (`t0_ns`,
`t1_ns`); the profiler trace keeps its own clock. This module

1. ties the two clocks: each `bench.serve`, `bench.refit.solve` and
   `bench.refit.swap` annotation of the trace is paired with the root
   program span that starts inside it nearest its start (the first); the
   offset is the median of the start differences, and the residual is
   printed;
2. splits every idle interval of the device across the innermost program
   spans open over it, by overlap; what no span covers is
   `unattributed`. The whole table goes to standard error;
3. prints the serve calls whose thread stood still longest (wall time
   less CPU time less device waits and copies), with their page faults
   and involuntary context switches, and how far the refit spans cover
   the benchmark's own refit clocks.

Readers call `analyse(run)`, which does this once per run. Every
function here returns None where the run holds nothing to read: spans
without `t0_ns` come from a program that does not time them so.
"""
from __future__ import annotations

import collections
import sys

import numpy as np

import trace_reduce

PAIRED = ("bench.serve", "bench.refit.solve", "bench.refit.swap")
TOLERANCE_NS = 250_000       # how near its annotation's start a span starts
UNATTRIBUTED = "unattributed"


def log(msg: str) -> None:
    print(f"[span_reduce] {msg}", file=sys.stderr, flush=True)


def program_spans(run) -> list[dict]:
    """The run's program spans that carry `perf_counter_ns` times: the
    serve window's, or, where the run kept none, the recorder's ring."""
    spans = run.spans
    if not spans:
        from repro import obs
        spans = obs.SPANS.to_list()
    return [s for s in spans if "t0_ns" in s]


def roots(spans: list[dict]) -> list[dict]:
    return [s for s in spans if s["parent"] < 0 and s["name"] != "compile"]


def children(spans: list[dict]) -> dict[int, list[dict]]:
    out: dict[int, list[dict]] = collections.defaultdict(list)
    for s in spans:
        out[s["parent"]].append(s)
    return out


def _pair(mapped: np.ndarray, a_s: np.ndarray, a_e: np.ndarray,
          tol: float) -> np.ndarray:
    """Per annotation [s, e], the index of the mapped root start in
    [s - tol, e] nearest s, or -1."""
    after = np.searchsorted(mapped, a_s, "left")
    before = after - 1
    last = len(mapped) - 1
    ok_a = (after <= last) & (mapped[np.minimum(after, last)] <= a_e)
    ok_b = (before >= 0) & (mapped[np.maximum(before, 0)] >= a_s - tol)
    closer_b = ok_b & (~ok_a | (a_s - mapped[np.maximum(before, 0)]
                                < mapped[np.minimum(after, last)] - a_s))
    return np.where(closer_b, before, np.where(ok_a, after, -1))


def tie_clock(host, spans: list[dict], window: tuple[float, float],
              tol: float = TOLERANCE_NS) -> float | None:
    """Offset (ns) that takes a span's `perf_counter_ns` time to the
    trace's clock, or None when the annotations and root spans do not
    pair one to one. Prints the residual of the pairs."""
    ann = [(s, e) for n, s, e in host if n in PAIRED]
    starts = np.sort(np.array([s["t0_ns"] for s in roots(spans)],
                              np.float64))
    if not ann or not len(starts):
        log(f"clock tie: {len(ann)} annotations, {len(starts)} root spans")
        return None
    a_s = np.array([s for s, _ in ann], np.float64)
    a_e = np.array([e for _, e in ann], np.float64)
    # The first annotation's span is one of the roots: try each. Score an
    # offset by the annotations it starts a root within tol of (one holds
    # several roots, so containment alone aliases), less the roots it
    # puts in the window outside every annotation (set-up's, shifted in).
    lo, hi = window
    best, best_n = 0.0, -len(starts) - 1
    for d in a_s[0] - starts:
        mapped = starts + d
        pair = _pair(mapped, a_s, a_e, tol)
        near = int((mapped[pair[pair >= 0]] <= a_s[pair >= 0] + tol).sum())
        m = mapped[(mapped >= lo) & (mapped <= hi)]
        j = np.searchsorted(a_s - tol, m, "right") - 1
        outside = int(((j < 0) | (m > a_e[np.maximum(j, 0)] + tol)).sum())
        if near - outside > best_n:
            best, best_n = d, near - outside
    pair = _pair(starts + best, a_s, a_e, tol)
    if (pair < 0).any() or len(set(pair.tolist())) != len(pair):
        log(f"clock tie: {int((pair >= 0).sum())} of {len(ann)} "
            "annotations paired one to one; no tie")
        return None
    diffs = a_s - starts[pair]
    offset = float(np.median(diffs))
    resid = np.abs(diffs - offset) * 1e-6
    log(f"clock tie: {len(ann)} pairs, residual p50 "
        f"{np.percentile(resid, 50):.4f} ms, p99 "
        f"{np.percentile(resid, 99):.4f} ms, max {resid.max():.4f} ms")
    return offset


def innermost(spans: list[dict], offset: float, lo: float,
              hi: float) -> list[tuple[float, float, str]]:
    """Disjoint, sorted pieces of [lo, hi], each named by the innermost
    span open over it (trace clock)."""
    ivs = sorted((s["t0_ns"] + offset, s["t1_ns"] + offset, s["depth"],
                  s["name"]) for s in spans)
    out: list[tuple[float, float, str]] = []

    def emit(a, b, name):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b, name))
    stack: list[list] = []          # [end, name], outermost first
    cursor = lo
    for s, e, _, name in ivs:
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(cursor, end, top)
            cursor = max(cursor, end)
        if stack:
            emit(cursor, s, stack[-1][1])
            e = min(e, stack[-1][0])    # a child ends with its parent
        cursor = max(cursor, s)
        stack.append([e, name])
    while stack:
        end, top = stack.pop()
        emit(cursor, end, top)
        cursor = max(cursor, end)
    return out


def split_idle(trace, spans: list[dict], offset: float) -> dict[str, float]:
    """Idle seconds of the window by innermost span name, averaged over
    the devices, with `unattributed` for idle time no span covers."""
    lo, hi = trace.window
    segs = innermost(spans, offset, lo, hi)
    n_dev = max(1, len(trace.ops))
    out: collections.Counter = collections.Counter()
    for evs in trace.ops.values():
        busy = trace_reduce.merge(evs, lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        k = 0
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            covered = 0.0
            while k < len(segs) and segs[k][1] <= a:
                k += 1
            j = k
            while j < len(segs) and segs[j][0] < b:
                part = min(b, segs[j][1]) - max(a, segs[j][0])
                out[segs[j][2]] += part
                covered += part
                j += 1
            out[UNATTRIBUTED] += (b - a) - covered
    return {name: v * 1e-9 / n_dev for name, v in out.items()}


def stall_ms(span: dict, kids: list[dict]) -> float | None:
    """Wall time of a root span in which its thread neither ran nor
    waited on the device: wall - CPU - children's syncs and fetches."""
    if "cpu_ms" not in span:
        return None
    waits = sum(c["sync_ms"] + c.get("fetch_ms", 0.0) for c in kids)
    return span["wall_ms"] - span["cpu_ms"] - waits


def log_stalls(spans: list[dict], offset: float | None,
               window: tuple[float, float], k: int = 5) -> None:
    lo, hi = window
    kids = children(spans)
    calls = [(stall_ms(s, kids[s["id"]]), s) for s in spans
             if s["name"] == "serve" and s["parent"] < 0
             and (offset is None or lo <= s["t0_ns"] + offset <= hi)]
    calls = sorted((c for c in calls if c[0] is not None),
                   key=lambda c: -c[0])
    for stall, s in calls[:k]:
        at = "" if offset is None else \
            f" at {(s['t0_ns'] + offset - lo) * 1e-9:.3f} s"
        parts = ", ".join(
            f"{c['name']} {c['wall_ms']:.1f}"
            + (f" (sync {c['sync_ms']:.1f}, fetch {c.get('fetch_ms', 0):.1f}"
               f" for {c.get('fetch_bytes', 0) / 2**20:.1f} MiB)"
               if c["name"].endswith("_match") else "")
            for c in kids[s["id"]])
        log(f"serve stall {stall:.1f} ms{at}: wall {s['wall_ms']:.1f}, "
            f"cpu {s['cpu_ms']:.1f} ms, majflt {s['majflt']}, minflt "
            f"{s['minflt']}, nivcsw {s['nivcsw']}, batch {s.get('n')}; "
            f"{parts}")


def refit_spans(run) -> list[dict] | None:
    """Program spans of the window's refits: from the root `prune` span of
    the first of the last `len(run.refits)` refits on."""
    n = len(run.refits)
    spans = program_spans(run)
    prunes = [s for s in roots(spans) if s["name"] == "prune"]
    if not n or len(prunes) < n:
        return None
    t0 = prunes[-n]["t0_ns"]
    return [s for s in spans if s["t0_ns"] >= t0]


def wall_ms(spans: list[dict], *names: str, root: bool = False) -> float:
    return sum(s["wall_ms"] for s in spans if s["name"] in names
               and (not root or s["parent"] < 0))


def log_refit_cover(run) -> None:
    spans = refit_spans(run)
    if spans is None:
        return
    solve_ms = 1e3 * sum(r.solve_s for r in run.refits)
    swap_ms = 1e3 * sum(r.swap_s for r in run.refits)
    in_solve = wall_ms(spans, "prune", "solve", "tiering", "classify_log",
                       root=True)
    steps = wall_ms(spans, "greedy.step")
    in_swap = wall_ms(spans, "prepare", "rollout", "serve", root=True)
    log(f"refit cover: prune+solve+tiering+classify_log {in_solve:.1f} of "
        f"{solve_ms:.1f} ms ({100 * in_solve / solve_ms:.1f}%); "
        f"greedy.step {steps:.1f} of solve {wall_ms(spans, 'solve'):.1f} ms "
        f"({100 * steps / max(wall_ms(spans, 'solve'), 1e-9):.1f}%); "
        f"prepare+rollout+serve {in_swap:.1f} of {swap_ms:.1f} ms "
        f"({100 * in_swap / swap_ms:.1f}%)")


def analyse(run) -> dict[str, float] | None:
    """Tie, idle split and logs of a traced run, once per run (the idle
    seconds by innermost span name are kept on the run)."""
    if "span_idle_s" not in vars(run):
        vars(run)["span_idle_s"] = _analyse(run)
    return vars(run)["span_idle_s"]


def _analyse(run) -> dict[str, float] | None:
    spans = program_spans(run) if run.trace is not None else []
    if not spans:
        return None
    offset = tie_clock(run.trace.host, spans, run.trace.window)
    idle = None
    if offset is not None and run.trace.ops:
        idle = split_idle(run.trace, spans, offset)
        log(f"idle by innermost span: {sum(idle.values()):.3f} s of "
            f"{run.trace.window_s:.3f} s")
        for name, v in sorted(idle.items(), key=lambda x: -x[1]):
            log(f"  {name:<14} {v:10.4f} s  "
                f"{100 * v / run.trace.window_s:7.3f}%")
    log_stalls(spans, offset, run.trace.window)
    log_refit_cover(run)
    return idle


def idle_pct(run, name: str) -> float | None:
    """Share of the window the device sat idle while `name` was the
    innermost program span (`unattributed`: while none was open), %."""
    idle = analyse(run)
    if idle is None:
        return None
    return 100.0 * idle.get(name, 0.0) / run.trace.window_s


# -- arithmetic of the readers in layer_metrics/ -----------------------------

def serve_ms_per_query(run, part) -> float | None:
    """`part(span, its children)` summed over the window's root `serve`
    spans, over the queries they served."""
    spans = [s for s in run.spans if "t0_ns" in s]
    kids = children(spans)
    calls = [s for s in spans if s["name"] == "serve" and s["parent"] < 0]
    n = sum(s.get("n", 0) for s in calls)
    values = [part(s, kids[s["id"]]) for s in calls]
    if not n or None in values:
        return None
    return sum(values) / n


def self_ms(span: dict, kids: list[dict]) -> float:
    return span["wall_ms"] - sum(c["wall_ms"] for c in kids)


def match_ms(field: str):
    """`field` summed over a serve span's `t1_match`/`t2_match` children."""
    def part(span, kids):
        return sum(c.get(field, 0.0) for c in kids
                   if c["name"] in ("t1_match", "t2_match"))
    return part


def per_refit_ms(run, *names: str) -> float | None:
    """Wall time of the window's `names` spans per refit."""
    spans = refit_spans(run)
    if spans is None:
        return None
    return wall_ms(spans, *names) / len(run.refits)


def greedy_step_ms(run, part) -> float | None:
    """`part(step span, its children)` over the window's greedy steps."""
    spans = refit_spans(run)
    steps = [s for s in spans or () if s["name"] == "greedy.step"]
    if not steps:
        return None
    kids = children(spans)
    return sum(part(s, kids[s["id"]]) for s in steps) / len(steps)
