"""One run of one cell: set-up, a measured window, metrics and checks.

Everything a cell needs is found by name from `BENCHMARK.json`: its
configuration in `configs/`, its traffic mix in `mixes/`, and each
per-layer metric's reader in `layer_metrics/<metric>.py`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_traces")
N_CHECKED = 512          # served answers compared id for id


class BenchError(Exception):
    """The cell cannot run here; no result is printed."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(os.path.join(ROOT, conf["file"])),
                mix=load_json(os.path.join(HERE, "mixes",
                                           w["traffic"] + ".json")),
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def read_layer_metric(name: str, run) -> float | None:
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Stalls:
    """What stalls the caller for as long as it takes: programs JAX
    compiled or loaded from its persistent cache, seen by its monitoring
    hook (`loaded` counts the cache hits among `n`), and the interpreter's
    garbage-collection pauses, (start, seconds, generation) each."""

    def __init__(self):
        import jax
        self.n = 0
        self.loaded = 0
        self.gc: list = []
        self._gc_start = 0.0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.loaded += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        else:
            self.gc.append((self._gc_start, now - self._gc_start,
                            info["generation"]))


def device_info(chips: int) -> dict:
    import jax
    devs = jax.local_devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader may read."""
    cell: Cell
    trace: object = None                    # trace_reduce.Trace
    spans: list = dataclasses.field(default_factory=list)
    n_served: int = 0                       # queries answered in the window
    latency_ms: np.ndarray | None = None    # of each query answered
    match_bytes: float = 0.0                # least HBM bytes of those matches
    compiles: int = 0                       # backend compiles in the window
    gc: list = dataclasses.field(default_factory=list)  # pauses in the window
    refits: list = dataclasses.field(default_factory=list)
    greedy_step_bytes: float = 0.0
    peaks: dict = dataclasses.field(default_factory=dict)


def setup(cell: Cell, seed: int):
    """Data from the seed, then the program's own path: mine, solve,
    deploy one shard with one replica per tier, no result cache."""
    import corpus
    from repro import api
    cfg = cell.config
    t = time.perf_counter()
    data = corpus.make(cfg, seed)
    pcorpus, plog = corpus.to_program(data)
    log(f"data: {data.n_docs} docs x {data.vocab_size} terms, "
        f"{len(data.queries)} queries, {len(data.doc)} pairs "
        f"({time.perf_counter() - t:.1f} s)")
    t = time.perf_counter()
    pipe = api.TieringPipeline.from_corpus(pcorpus, plog).mine(
        min_support=cfg["min_support"], max_clause_len=cfg["max_clause_len"])
    log(f"mine: {len(pipe.data.clauses)} clauses "
        f"({time.perf_counter() - t:.1f} s)")
    t = time.perf_counter()
    pipe.solve(cfg["solver"], budget_frac=cfg["budget_frac"])
    log(f"solve: {pipe.summary()} ({time.perf_counter() - t:.1f} s)")
    t = time.perf_counter()
    fleet = pipe.deploy_cluster(n_shards=cfg["n_shards"],
                                t1_replicas=cfg["t1_replicas"],
                                t2_replicas=cfg["t2_replicas"], cache=None)
    log(f"deploy: {fleet.describe()} ({time.perf_counter() - t:.1f} s)")
    return data, pipe, fleet


def check(name: str, value, limit) -> dict:
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(value <= limit)}


def serve_cell(cell: Cell, seed: int, seconds: float, tracer, run: Run,
               t_start: float, stalls: Stalls, control: bool):
    import reference
    import serve_loop
    import traffic
    data, pipe, fleet = setup(cell, seed)
    mix = cell.mix
    clauses = set(tuple(sorted(c)) for c in pipe.tiering().clauses)
    elig = [reference.eligible(q, clauses) for q in data.queries]
    t = time.perf_counter()
    calls = serve_loop.warm_shapes(fleet, data.queries, elig,
                                   int(mix["max_batch"]))
    log(f"warm-up: {calls} serve calls ({time.perf_counter() - t:.1f} s, "
        f"{stalls.n} programs so far, {stalls.loaded} of them from the "
        "persistent cache)")
    load = traffic.open_loop(mix, data.test_weights, seed, seconds)
    n = len(load.arrivals)
    check_idx = set(traffic.stream(seed, 4).choice(
        n, size=min(n, N_CHECKED), replace=False).tolist())
    gc.collect()
    setup_s = time.perf_counter() - t_start
    c0, g0, spans0 = stalls.n, len(stalls.gc), _span_seq()
    with tracer():
        win = serve_loop.run(fleet, data.queries, load.arrivals,
                             load.query_ids, int(mix["max_batch"]),
                             check_idx, tracer.annotate)
    run.compiles = stalls.n - c0
    run.gc = stalls.gc[g0:]
    run.spans = _spans_since(spans0)
    answered = win.answer_len >= 0
    run.n_served = int(answered.sum())
    width = fleet.router.shards[-1].word_hi
    lens = np.array([len(q) for q in data.queries])[load.query_ids]
    run.match_bytes = float(((lens + 1) * width * 4)[answered].sum())
    device = device_info(cell.chips)
    lat_ms = run.latency_ms = win.latency_s[answered] * 1e3
    values = {
        "setup_s": setup_s,
        "serve_p50_ms": float(np.percentile(lat_ms, 50)),
    }
    log_slow_calls(win, run)
    log(f"window: {n} queries due in {seconds} s at {mix['rate_qps']} q/s, "
        f"{len(win.batches)} calls, mean batch {n / len(win.batches):.2f}, "
        f"last answer at {win.elapsed_s:.3f} s, {run.compiles} compiles, "
        f"p50 {values['serve_p50_ms']:.3f} ms, "
        f"p95 {np.percentile(lat_ms, 95):.3f} ms, "
        f"p99 {np.percentile(lat_ms, 99):.3f} ms, completed in window "
        f"{(win.done_s[answered] <= seconds).sum() / seconds:.2f} q/s")

    # -- correctness, after the window, against the plain reference --------
    tier1_docs = np.asarray(pipe.tiering().tier1_docs, bool)
    del pipe, fleet
    gc.collect()
    t = time.perf_counter()
    post = reference.Postings(data.doc, data.term, data.n_docs,
                              data.vocab_size)
    got_ids = dict(win.answers)
    got_ids[win.longest[0]] = win.longest[1]
    got_len, got_t1 = win.answer_len, [t1 for *_, t1 in win.batches]
    if control:
        got_len, got_ids, got_t1 = tier1_only(
            post, tier1_docs, data.queries, load.query_ids, answered,
            list(got_ids), win.batches)
    counts: dict[int, int] = {}
    wrong_counts = 0
    for k in np.flatnonzero(answered):
        qid = int(load.query_ids[k])
        if qid not in counts:
            counts[qid] = post.count(data.queries[qid])
        wrong_counts += int(got_len[k] != counts[qid])
    wrong_ids = sum(
        not np.array_equal(ids, post.ids(data.queries[load.query_ids[k]]))
        for k, ids in got_ids.items())
    tier1_off = sum(t1 != sum(elig[q] for q in load.query_ids[i:j])
                    for (i, j, _), t1 in zip(win.batches, got_t1))
    checks = [
        check("unanswered", int(n - run.n_served), 0),
        check("wrong_ids", int(wrong_ids), 0),
        check("wrong_counts", int(wrong_counts), 0),
        check("tier1_batches_off", int(tier1_off), 0),
    ]
    log(f"reference: {len(got_ids)} answers id for id (largest "
        f"{len(win.longest[1])} ids), {run.n_served} by count, "
        f"{len(win.batches)} batches' Tier-1 counts "
        f"({time.perf_counter() - t:.1f} s)")
    return values, checks, device, n, int(n - run.n_served)


def tier1_only(post, tier1_docs, queries, query_ids, answered, keys,
               batches):
    """The control, in the program's place: every query served from Tier 1
    alone, which breaks the guarantee that a query no selected clause
    covers gets its whole match set (Theorem 3.1). Returns what the window
    would have returned: answer sizes, the checked answers' ids, and each
    batch's Tier-1 count."""
    mask = post.pack(tier1_docs)
    counts: dict[int, int] = {}
    lens = np.full(len(query_ids), -1, np.int64)
    for k in np.flatnonzero(answered):
        qid = int(query_ids[k])
        if qid not in counts:
            counts[qid] = post.count(queries[qid], mask)
        lens[k] = counts[qid]
    ids = {k: post.ids(queries[query_ids[k]], mask) for k in keys}
    return lens, ids, [j - i for i, j, _ in batches]


def log_slow_calls(win, run: Run, k: int = 5) -> None:
    """Where the longest serve calls of the window went: the program's
    spans that started inside each (wall and device-sync ms) and the
    garbage-collection pauses in it."""
    log(f"gc in the window: {len(run.gc)} pauses, "
        f"{sum(p[1] for p in run.gc) * 1e3:.1f} ms, generation-2 "
        f"{sum(p[2] == 2 for p in run.gc)}; median serve call "
        f"{np.median(win.call_s) * 1e3:.2f} ms")
    for c in np.argsort(win.call_s)[::-1][:k]:
        lo, hi = win.call_at[c], win.call_at[c] + win.call_s[c]
        parts = [f"{sp['name']} {sp['wall_ms']:.1f}"
                 + (f" (sync {sp['sync_ms']:.1f})" if sp.get("sync_ms")
                    else "")
                 for sp in run.spans
                 if lo <= sp["t0_s"] - win.wall_offset <= hi]
        paused = sum(p[1] for p in run.gc if lo <= p[0] <= hi)
        i, j, _ = win.batches[c]
        log(f"serve call {win.call_s[c] * 1e3:.1f} ms, batch {j - i}, at "
            f"{lo - win.opened:.2f} s (wall {lo + win.wall_offset:.3f}): "
            f"{', '.join(parts)} ms; gc {paused * 1e3:.1f} ms")


def refit_cell(cell: Cell, seed: int, seconds: float, tracer, run: Run,
               t_start: float, stalls: Stalls, control: bool):
    import reference
    import refit_loop
    import traffic
    data, pipe, fleet = setup(cell, seed)
    mix = cell.mix
    steps = traffic.refit_rotate(mix, data.test_weights, data.train_weights)
    loop = refit_loop.Loop(pipe, fleet, steps, float(mix["prune_below"]),
                           tracer.annotate)
    t = time.perf_counter()
    warm = loop.cycle(keep_docs=False)
    log(f"warm-up cycle: {[r.kind for r in warm]} "
        f"({time.perf_counter() - t:.1f} s, {stalls.n} programs so far, "
        f"{stalls.loaded} of them from the persistent cache)")
    prob = pipe.problem
    run.greedy_step_bytes = float(
        prob.clause_doc_bits.size * 4 + prob.clause_query_bits.size * 4)
    gc.collect()
    setup_s = time.perf_counter() - t_start
    c0, g0 = stalls.n, len(stalls.gc)
    refits: list = []
    with tracer():
        t0 = time.perf_counter()
        while True:
            refits += loop.cycle(keep_docs=not refits)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    run.compiles = stalls.n - c0
    run.gc = stalls.gc[g0:]
    run.refits = refits
    device = device_info(cell.chips)
    values = {"setup_s": setup_s, "refit_s": elapsed / len(refits)}
    log(f"window: {len(refits)} refits in {elapsed:.3f} s "
        f"({len(refits) // len(steps)} cycles), kinds "
        f"{[r.kind for r in refits[:len(steps)]]}, steps "
        f"{[len(r.order) for r in refits[:len(steps)]]}"
        f", {run.compiles} compiles, gc {len(run.gc)} pauses "
        f"{sum(p[1] for p in run.gc) * 1e3:.1f} ms, "
        f"refit_s {values['refit_s']:.4f}")

    clauses = list(pipe.data.clauses)
    budget = float(pipe.config.budget)
    base = loop.base_selected
    del loop, pipe, fleet, prob
    gc.collect()
    t = time.perf_counter()
    post = reference.Postings(data.doc, data.term, data.n_docs,
                              data.vocab_size)
    ref = reference.Greedy(post, clauses, data.queries)
    prune_below = float(mix["prune_below"])
    if control:
        refits = bf16_refits(ref, post, data.queries, steps, base, budget,
                             prune_below, refits)
    first = refits[:len(steps)]
    gaps = []
    prune_off = fallback_off = left = over = docs_off = 0
    probe_wrong = probe_t1 = 0
    prev = base
    for r in first:
        w = steps[r.position].weights
        kept, near = ref.prune(prev, w, prune_below)
        prune_off += len(set(kept.tolist()) ^ set(r.kept.tolist())
                         - set(near.tolist()))
        cold = ref.coverage(r.warm_selected, w) + 1e-9 < ref.coverage(base, w)
        fallback_off += int(cold != (r.kind == "cold"))
        if set(r.order) | set(r.prefix.tolist()) != set(
                np.flatnonzero(r.selected).tolist()):
            gaps.append(1.0)        # the selection is not prefix + choices
        else:
            gap, more = ref.replay(w, budget, r.prefix, r.order)
            gaps.append(gap)
            left += int(more)
        union = ref.union(r.selected)
        over += int(np.bitwise_count(union).sum() > budget)
        docs_off += int((post.unpack(union).astype(bool)
                         != r.tier1_docs).sum())
        chosen = set(ref.clauses[i] for i in np.flatnonzero(r.selected))
        probe = [data.queries[i] for i in steps[r.position].probe_ids]
        probe_wrong += sum(not np.array_equal(a, post.ids(q))
                           for a, q in zip(r.probe_answers, probe))
        probe_t1 += int(r.probe_tier1 != sum(
            reference.eligible(q, chosen) for q in probe))
        prev = r.selected
    repeat = sum(
        not np.array_equal(r.selected, first[r.position].selected)
        or any(not np.array_equal(a, b) for a, b in
               zip(r.probe_answers, first[r.position].probe_answers))
        for r in refits[len(steps):])
    log(f"reference: {len(first)} refits replayed, choice gaps {gaps} "
        f"({time.perf_counter() - t:.1f} s)")
    checks = [
        check("prune_off", prune_off, 0),
        check("fallback_off", fallback_off, 0),
        check("choice_gap", max(gaps), float(mix["choice_gap_limit"])),
        check("stopped_early", left, 0),
        check("over_budget", over, 0),
        check("tier1_docs_off", docs_off, 0),
        check("probe_wrong", int(probe_wrong), 0),
        check("probe_tier1_off", probe_t1, 0),
        check("cycles_differ", int(repeat), 0),
    ]
    return values, checks, device, len(refits), 0


def bf16_refits(ref, post, queries, steps, base, budget, prune_below,
                refits):
    """The control, in the program's place: every refit of the window made
    by the reference's prune, its greedy in bfloat16 (the configuration
    states float32 weights) and its cold fallback. Returns the records the
    program's loop would have made."""
    import reference
    by_position: dict = {}
    prev = base
    for r in refits[:len(steps)]:
        w = steps[r.position].weights
        kept, _ = ref.prune(prev, w, prune_below)
        order = ref.solve(w, budget, kept, bf16=True)
        warm = np.zeros(len(ref.clauses), bool)
        warm[kept] = True
        warm[order] = True
        kind, prefix, selected = "warm", kept, warm
        if ref.coverage(warm, w) + 1e-9 < ref.coverage(base, w):
            kind, prefix = "cold", np.zeros(0, np.int64)
            order = ref.solve(w, budget, prefix, bf16=True)
            selected = np.zeros(len(ref.clauses), bool)
            selected[order] = True
        chosen = set(ref.clauses[i] for i in np.flatnonzero(selected))
        probe = [queries[i] for i in steps[r.position].probe_ids]
        by_position[r.position] = dataclasses.replace(
            r, kind=kind, kept=kept, warm_selected=warm, prefix=prefix,
            selected=selected, order=order,
            tier1_docs=post.unpack(ref.union(selected)).astype(bool),
            probe_answers=[post.ids(q) for q in probe],
            probe_tier1=sum(reference.eligible(q, chosen) for q in probe))
        prev = selected
    return [by_position[r.position] for r in refits]


RUNNERS = {"open_loop": serve_cell, "refit_rotate": refit_cell}


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout, for every
    program however quick to compile: later runs there load, not compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _span_seq() -> int:
    from repro import obs
    return obs.SPANS.seq


def _spans_since(seq: int) -> list:
    from repro import obs
    if obs.SPANS.seq - seq > len(obs.SPANS.ring):
        log(f"span ring dropped {obs.SPANS.seq - seq - len(obs.SPANS.ring)}"
            " spans of the window")
    return obs.SPANS.since(seq)


class Tracer:
    """Profiler trace of the window when asked for; the window and the
    benchmark's own calls are host annotations in it."""

    def __init__(self, on: bool, directory: str):
        self.on = on
        self.directory = directory

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def __call__(self):
        import jax
        if not self.on:
            yield
            return
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host annotations, no Python calls
        jax.profiler.start_trace(self.directory, profiler_options=options)
        try:
            with self.annotate("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, spec: dict | None = None,
             keep_trace: bool = False, control: bool = False,
             compile_cache: bool = True) -> dict:
    """`control` puts the control's output in the program's place before
    the checks; tests turn the persistent `compile_cache` off."""
    import jax
    import trace_reduce
    cell = load_cell(name, spec)
    if compile_cache:
        enable_compile_cache()
    stalls = Stalls()
    run = Run(cell=cell)
    tracer = Tracer(trace, os.path.join(TRACE_DIR, f"{name}-{seed}"))
    values, checks, device, attempted, failed = RUNNERS[cell.mix["kind"]](
        cell, seed, seconds, tracer, run, t_start, stalls, control)
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": int(attempted), "failed": int(failed)}
    if trace:
        run.peaks = peaks_for(device["kind"])
        run.trace = trace_reduce.load(trace_reduce.find_xplane(
            tracer.directory))
        metrics = {}
        for m in cell.per_layer:
            v = read_layer_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        result["breakdown"] = trace_reduce.breakdown(run.trace)
        if not keep_trace:
            shutil.rmtree(tracer.directory, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    return result
