#!/usr/bin/env python3
"""Chip smoke test: the tiered search fleet's main path on a TPU.

    python chip_smoke.py              # one v5e chip: a 2^21-passage index
    python chip_smoke.py --chips 4    # one four-chip host: 2^23 passages in
                                      # four shards, fused mesh serving

One process drives every phase through the entry points a user calls, on
data made from `--seed`:

    TieringPipeline.from_synthetic(scale="passage") -> mine -> solve("greedy")
    -> deploy_cluster -> serve

Every served match set is checked bit for bit against a plain numpy AND of
the query's postings rows at global width, and every eligibility bit
against `ClauseTiering.classify_queries`. The script exits nonzero, and
prints no result, when a check fails, when a main-path kernel does not
resolve to its Pallas kernel, and when JAX finds no TPU: it never falls
back to the CPU.

The lines before the last are smoke timings of one cold run, not metrics.
The last line is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MAIN_PATH_OPS = ("clause_match", "bit_matvec", "coverage_gain")
SERVE_SPANS = ("serve", "classify", "t1_match", "t2_match", "mesh_fused",
               "merge")
DOCS_PER_CHIP = 2 ** 21
N_BATCHES, BATCH = 4, 512          # queries drawn from the test weights
GIB = 2 ** 30


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Phases:
    """Wall seconds and backend-compile seconds per phase."""

    def __init__(self):
        import jax
        self.compile_s = 0.0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        say(f"phase {name}: {time.perf_counter() - t0:.3f} s wall, "
            f"{self.compile_s - c0:.3f} s compiling (smoke timing)")


def reference_ids(postings, query, n_docs: int):
    """Plain host reference: AND of the query's postings rows, unpacked."""
    import numpy as np
    row = postings[query[0]].copy()
    for t in query[1:]:
        row &= postings[t]
    bits = np.unpackbits(row.view(np.uint8), bitorder="little")
    return np.flatnonzero(bits[:n_docs])


def device_bytes() -> dict:
    import jax
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out[d.id] = (stats.get("bytes_in_use", 0),
                     stats.get("peak_bytes_in_use", 0),
                     stats.get("bytes_limit", 0))
    return out


def check_placement(fleet) -> None:
    """Four-chip layout: each shard's Tier-2 slice, Tier-1 sub-index and
    route-table block sit on that shard's device and nowhere else."""
    import jax
    devs = jax.devices()          # shard_mesh's device order
    for tier, groups in (("t1", fleet.router.t1), ("t2", fleet.router.t2)):
        for s, group in enumerate(groups):
            for rep in group:
                got = {d.id for d in rep.postings.devices()}
                check(got == {devs[s].id},
                      f"{tier} shard {s} resident on devices {got}")
    for table in fleet.router._mesh_tables.values():
        for sh in table.tiers.addressable_shards:
            s = sh.index[0].start or 0
            check(sh.device.id == devs[s].id,
                  f"route-table block {s} on device {sh.device.id}")
            say(f"route-table block {s} (shards {sh.index[0]}) on device "
                f"{sh.device.id}: {sh.data.nbytes / GIB:.3f} GiB")


def run(chips: int, seed: int, n_batches: int, batch: int, *,
        scale: str = "passage", n_docs: int | None = None,
        require_pallas: bool = True) -> None:
    """Drive the main path once and check every answer; raises
    `SmokeFailure` (or the error of the phase that broke) on failure."""
    import numpy as np
    from repro import api, distributed, obs

    phase = Phases()
    mesh = distributed.use_mesh(distributed.shard_mesh(chips)) \
        if chips > 1 else contextlib.nullcontext()
    with mesh:
        plan = distributed.current_plan()
        placements = {op: plan.placement(op) for op in MAIN_PATH_OPS}
        say(f"placements {placements}; shard-fused serving: "
            f"{plan.shard_fused} over {plan.n_shard_devices} device(s)")
        if require_pallas:
            check(all(p == "pallas" for p in placements.values()),
                  f"main-path kernels not on Pallas: {placements}")

        with phase("data"):
            pipe = api.TieringPipeline.from_synthetic(seed=seed, scale=scale,
                                                      n_docs=n_docs)
        say(f"corpus: {pipe.corpus.n_docs} docs x {pipe.corpus.vocab_size} "
            f"terms, {pipe.log.n_queries} unique queries")
        with phase("mine"):
            pipe.mine(min_support=1e-3)
        with phase("solve"):
            pipe.solve("greedy", budget_frac=0.5)
        say(f"{pipe.summary()}")
        with phase("verify (Theorem 3.1)"):
            check(pipe.verify(), "Theorem 3.1 fails on the query log")
        with phase("deploy"):
            fleet = pipe.deploy_cluster(n_shards=chips, t1_replicas=1)
        tiering = pipe.tiering()
        prob = pipe.problem
        t2 = [g[0].postings for g in fleet.router.t2]
        t1 = [g[0].postings for g in fleet.router.t1]
        say(f"resident: Tier-2 postings {sum(x.nbytes for x in t2) / GIB:.3f}"
            f" GiB, Tier-1 sub-index {sum(x.nbytes for x in t1) / GIB:.3f} "
            f"GiB, clause_doc_bits {prob.clause_doc_bits.nbytes / GIB:.3f} "
            f"GiB, clause_query_bits {prob.clause_query_bits.nbytes / GIB:.4f}"
            f" GiB; {fleet.describe()}")

        log, postings = pipe.log, pipe.data.postings
        rng = np.random.default_rng(seed + 1)
        draws = rng.choice(log.n_queries, size=(n_batches, batch),
                           p=log.test_weights)
        n_checked = n_tier1 = 0
        for i, idx in enumerate(draws):
            queries = [log.queries[j] for j in idx]
            with phase(f"serve batch {i} ({batch} queries)"):
                served = fleet.serve(queries)
            trace = fleet.trace[-1]
            elig = np.asarray(fleet.classify(queries))
            want = tiering.classify_queries(log.query_bits[idx])
            check(np.array_equal(elig, want),
                  f"batch {i}: eligibility differs from classify_queries")
            check(trace.n_tier1 == int(want.sum()) and trace.consistent,
                  f"batch {i}: trace {trace}")
            with phase(f"host reference batch {i}"):
                for q, got in zip(queries, served):
                    ref = reference_ids(postings, q, pipe.corpus.n_docs)
                    check(np.array_equal(got, ref),
                          f"batch {i}: match set of {q} differs from the "
                          f"host reference ({len(got)} vs {len(ref)} docs)")
            n_checked += len(queries)
            n_tier1 += trace.n_tier1
        say(f"{n_checked} match sets equal the host reference bit for bit; "
            f"{n_tier1} served from Tier 1")
        spans = {name: sum(sp["wall_ms"] for sp in obs.SPANS.of_name(name))
                 for name in SERVE_SPANS}
        say("serve span wall ms over all batches (smoke timing): "
            + ", ".join(f"{k} {v:.1f}" for k, v in spans.items() if v))
        if chips > 1:
            check_placement(fleet)
    for dev, (used, peak, limit) in device_bytes().items():
        say(f"device {dev}: bytes_in_use {used / GIB:.3f} GiB, "
            f"peak_bytes_in_use {peak / GIB:.3f} GiB of {limit / GIB:.3f}")
    say(f"backend compile total {phase.compile_s:.3f} s (smoke timing)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one-chip index; 4: four shards, fused serving")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    cache = compile_cache.enable()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU found: JAX reports platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} TPU devices, "
              f"found {n_dev}", file=sys.stderr)
        return 2
    say(f"device {dev.device_kind} x{n_dev}; compile cache {cache}")
    try:
        run(args.chips, args.seed, N_BATCHES, BATCH,
            n_docs=DOCS_PER_CHIP * args.chips)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
