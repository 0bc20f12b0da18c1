"""Cluster serving section: strong scaling over shard count, the
latency-vs-budget frontier (global AND traffic-split budgets), a
retiered-vs-static A/B under drift, a global-vs-split budget A/B, and the
loadgen service-model calibration.

Question families (seeded, tiny scale by default so the section stays
CI-sized; REPRO_BENCH_CLUSTER_SCALE overrides):

  * strong scaling: with the doc space split over {1,2,4} Tier-2 shards,
    does per-shard words-scanned (the per-machine roofline term) drop with
    shard count, and what do simulated p50/p95/p99 and throughput do?
  * frontier: sweeping the Tier-1 budget trades fleet word traffic against
    simulated tail latency — the paper's cost argument as a curve — at the
    SAME totals once with a global knapsack and once with per-shard
    traffic-split caps (the Fig.-1 machines-vs-coverage economics, measured:
    fleet_words is the machines proxy, coverage the served fraction).
  * drift A/B: on identical windows, a re-tiering cluster (rolling swaps)
    vs the same fleet frozen — coverage, traffic saving, and loadgen
    latency on each arm's final tiering.
  * budget-split A/B: on identical drift windows at EQUAL total budget, a
    globally-budgeted fleet vs per-shard traffic-split caps (hot shards get
    bigger local Tier-1s; refits re-allocate the split).
  * calibration: fit `t_fixed + words * t_word` against measured
    `match_batch` wall times across sub-index widths at tiny/small scale;
    the coefficients + R² land in BENCH_cluster.json so `run_loadgen` can
    be driven with measured, not assumed, service times.
  * mesh_routing: fused shard_map serve (ONE SPMD program per batch over
    the `"shard"` device axis) vs the sequential per-shard host dispatch,
    measured batch-serve wall-clock at {1, 2, 4} forced host devices (each
    device count is a fresh subprocess — XLA fixes the device count at
    init).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import emit

CLUSTER_SCALE = os.environ.get("REPRO_BENCH_CLUSTER_SCALE", "tiny")
SHARD_SWEEP = (1, 2, 4)
AB_SCENARIOS = ("rotate", "churn")
N_WINDOWS = int(os.environ.get("REPRO_BENCH_CLUSTER_WINDOWS", "8"))
CALIBRATION_SCALES = tuple(os.environ.get(
    "REPRO_BENCH_CALIBRATION_SCALES", "tiny,small").split(","))


def _fresh_pipe(data):
    from repro import api
    return api.TieringPipeline.from_data(data).solve("greedy",
                                                     budget_frac=0.5)


def _loadgen(fleet, queries, **kw):
    from repro import cluster
    plan = cluster.ClusterPlan.of_cluster(fleet)
    return cluster.run_loadgen(plan, fleet.classify(queries),
                               n_queries=4000, seed=0, **kw)


def run() -> dict:
    from repro import stream
    from repro.data import incidence, synthetic

    corpus, log = synthetic.make_tiering_dataset(0, CLUSTER_SCALE)
    data = incidence.build_tiering_data(corpus, log, min_support=1e-3)
    sample = log.queries[:min(2048, log.n_queries)]
    results: dict[str, dict] = {}

    # -- strong scaling over shard count --------------------------------------
    pipe = _fresh_pipe(data)
    scaling = {}
    for n_shards in SHARD_SWEEP:
        fleet = pipe.deploy_cluster(n_shards=n_shards, t1_replicas=2)
        batch = sample[:512]
        t0 = time.perf_counter()
        fleet.serve(batch)
        dt = time.perf_counter() - t0
        per_shard_words = max(
            s.n_words for s in fleet.shards)           # t2 words/query/shard
        rep = _loadgen(fleet, sample)
        scaling[n_shards] = {
            "per_shard_t2_words_per_query": per_shard_words,
            "p50_ms": rep.p50_ms, "p95_ms": rep.p95_ms, "p99_ms": rep.p99_ms,
            "throughput_qps": rep.throughput_qps,
            "fleet_words": rep.fleet_words,
        }
        emit(f"cluster_shards{n_shards}", 1e6 * dt / len(batch),
             f"per_shard_t2_words={per_shard_words};p50={rep.p50_ms:.4f};"
             f"p95={rep.p95_ms:.4f};p99={rep.p99_ms:.4f};"
             f"qps={rep.throughput_qps:.0f};fleet_words={rep.fleet_words}",
             data={"latency_hist": rep.latency_hist})
    results["strong_scaling"] = scaling

    # -- latency-vs-budget frontier: global vs traffic-split caps -------------
    frontier = {}
    for frac in (0.25, 0.5, 0.75):
        from repro import api
        point = {}
        for arm in ("global", "split"):
            fp = api.TieringPipeline.from_data(data)
            if arm == "split":
                fp.solve("greedy", budget_frac=frac,
                         budget_split="traffic", n_shards=2)
            else:
                fp.solve("greedy", budget_frac=frac)
            fleet = fp.deploy_cluster(n_shards=2, t1_replicas=2)
            rep = _loadgen(fleet, sample)
            cov = fp.coverage()
            point[arm] = {"p95_ms": rep.p95_ms,
                          "fleet_words": rep.fleet_words,
                          "tier1_fraction": rep.tier1_fraction,
                          "test_coverage": cov["test"],
                          "caps": list(fp.result.extra["caps"])
                          if arm == "split" else None}
            emit(f"cluster_budget{int(100 * frac)}_{arm}", 0.0,
                 f"p95={rep.p95_ms:.4f};fleet_words={rep.fleet_words};"
                 f"t1_frac={rep.tier1_fraction:.4f};"
                 f"cov={cov['test']:.4f}")
        frontier[frac] = point
    results["frontier"] = frontier

    # -- retiered vs static A/B under drift -----------------------------------
    ab = {}
    for scenario in AB_SCENARIOS:
        kw = dict(scenario=scenario, n_windows=N_WINDOWS,
                  queries_per_window=256, seed=0)
        sp = _fresh_pipe(data)
        static_fleet = sp.deploy_cluster(n_shards=2, t1_replicas=2)
        static = stream.run_stream(sp, engine=static_fleet,
                                   enable_refit=False, **kw)
        rp = _fresh_pipe(data)
        retiered_fleet = rp.deploy_cluster(n_shards=2, t1_replicas=2)
        retiered = stream.run_stream(rp, engine=retiered_fleet, **kw)
        # a late-window refit can leave the rolling swap mid-flight; finish
        # it so the latency probe measures the FINAL tiering's topology
        retiered_fleet.drain_rollout()
        lat_s = _loadgen(static_fleet, sample)
        lat_r = _loadgen(retiered_fleet, sample)
        ab[scenario] = {
            "static_cov": static.mean_coverage,
            "retiered_cov": retiered.mean_coverage,
            "static_saving": static.cumulative.cost_saving,
            "retiered_saving": retiered.cumulative.cost_saving,
            "static_p95_ms": lat_s.p95_ms, "retiered_p95_ms": lat_r.p95_ms,
            "n_refits": retiered.n_refits,
            "pair_consistent": retiered_fleet.consistency_ok(),
        }
        emit(f"cluster_ab_{scenario}_static", 0.0,
             f"cov={static.mean_coverage:.4f};"
             f"saving={static.cumulative.cost_saving:.4f};"
             f"p95={lat_s.p95_ms:.4f}",
             data={"latency_hist": lat_s.latency_hist})
        emit(f"cluster_ab_{scenario}_retiered", 0.0,
             f"cov={retiered.mean_coverage:.4f};"
             f"saving={retiered.cumulative.cost_saving:.4f};"
             f"p95={lat_r.p95_ms:.4f};refits={retiered.n_refits};"
             f"consistent={retiered_fleet.consistency_ok()}",
             data={"latency_hist": lat_r.latency_hist})
    results["ab"] = ab

    # -- global vs traffic-split budgets under drift (equal total budget) -----
    from repro import api
    split_ab = {}
    for scenario in AB_SCENARIOS:
        kw = dict(scenario=scenario, n_windows=N_WINDOWS,
                  queries_per_window=256, seed=0)
        arms = {}
        for arm in ("global", "traffic"):
            p = api.TieringPipeline.from_data(data)
            if arm == "traffic":
                p.solve("greedy", budget_frac=0.5, budget_split="traffic",
                        n_shards=2)
            else:
                p.solve("greedy", budget_frac=0.5)
            fleet = p.deploy_cluster(n_shards=2, t1_replicas=2)
            rep = stream.run_stream(p, engine=fleet, **kw)
            fleet.drain_rollout()
            lat = _loadgen(fleet, sample)
            caps = p.result.extra.get("caps")
            arms[arm] = {
                "cov": rep.mean_coverage,
                "saving": rep.cumulative.cost_saving,
                "p95_ms": lat.p95_ms,
                "fleet_words": lat.fleet_words,
                "refits": rep.n_refits,
                "pair_consistent": fleet.consistency_ok(),
                "caps": None if caps is None else list(caps),
            }
            emit(f"cluster_split_{scenario}_{arm}", 0.0,
                 f"cov={rep.mean_coverage:.4f};"
                 f"saving={rep.cumulative.cost_saving:.4f};"
                 f"p95={lat.p95_ms:.4f};fleet_words={lat.fleet_words};"
                 f"refits={rep.n_refits}",
                 data={"latency_hist": lat.latency_hist})
        split_ab[scenario] = arms
    results["budget_split_ab"] = split_ab

    # -- loadgen service-model calibration ------------------------------------
    results["calibration"] = calibrate()

    # -- fused shard_map routing vs sequential host dispatch ------------------
    results["mesh_routing"] = mesh_routing()
    return results


_MESH_PROBE = r"""
import json, os, sys, time
import numpy as np
from repro import api, distributed as D

scale, n_shards, batch = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
pipe = (api.TieringPipeline.from_synthetic(seed=0, scale=scale)
        .mine(min_support=1e-3).solve("greedy", budget_frac=0.5))
queries = pipe.log.queries[:batch]


def wall(fleet, reps=9):   # min-of-reps: 1-core forced-device scheduling jitter
    fleet.serve(queries)                        # warm (compile + caches)
    best = min(
        (lambda t0: (fleet.serve(queries), time.perf_counter() - t0)[1])(
            time.perf_counter())
        for _ in range(reps))
    return 1e6 * best / len(queries)

host_fleet = pipe.deploy_cluster(n_shards=n_shards, t1_replicas=2)
host_us = wall(host_fleet)
a = host_fleet.serve(queries[:64])
mesh_fleet = pipe.deploy_cluster(n_shards=n_shards, t1_replicas=2)
with D.use_mesh(D.shard_mesh()):
    plan = D.current_plan()
    fused_us = wall(mesh_fleet)
    b = mesh_fleet.serve(queries[:64])      # parity probed ON the mesh path
assert all(np.array_equal(x, y) for x, y in zip(a, b)), "parity"
print(json.dumps({
    "devices": plan.n_shard_devices, "n_shards": n_shards,
    "fused_active": plan.shard_fused, "host_us_per_query": round(host_us, 3),
    "fused_us_per_query": round(fused_us, 3)}))
"""


def mesh_routing(device_counts=(1, 2, 4), n_shards: int = 4,
                 batch: int = 512) -> dict:
    """Fused vs host dispatch at forced host-device counts (subprocesses:
    the device count is fixed at jax init). At 1 device the plan gates the
    fusion off, so both arms measure the host path — the honest baseline.

    CPU-only rehearsal: the probes force `JAX_PLATFORMS=cpu` virtual
    devices, so these rows time XLA's CPU backend, never a chip."""
    out = {}
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    for ndev in device_counts:
        env = dict(os.environ,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=src + os.pathsep * bool(
                       os.environ.get("PYTHONPATH", ""))
                   + os.environ.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, "-c", _MESH_PROBE, CLUSTER_SCALE,
             str(n_shards), str(batch)],
            capture_output=True, text=True, env=env, timeout=900)
        if proc.returncode != 0:
            out[ndev] = {"error": proc.stderr[-500:]}
            continue
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        out[ndev] = rec
        emit(f"cluster_mesh_d{ndev}", rec["fused_us_per_query"],
             f"host_us={rec['host_us_per_query']};"
             f"fused_us={rec['fused_us_per_query']};"
             f"shards={rec['n_shards']};fused_active={rec['fused_active']}")
    return out


def _timed(fn, *args) -> float:
    t0 = time.perf_counter()
    fn(*args).block_until_ready()
    return time.perf_counter() - t0


def calibrate(scales: tuple[str, ...] = CALIBRATION_SCALES) -> dict:
    """Fit the loadgen service model against MEASURED `match_batch` walls.

    Sub-index width is the model's `words` variable, so slicing the packed
    postings to several widths (and spanning dataset scales) sweeps it;
    wall time per query at each width is one warm-started jitted call.
    """
    import jax.numpy as jnp

    from repro import cluster as cluster_pkg
    from repro.data import incidence, synthetic
    from repro.serve import matching

    words_l, us_l = [], []
    for scale in scales:
        corpus, log = synthetic.make_tiering_dataset(0, scale)
        postings = incidence.build_postings(corpus)
        toks = jnp.asarray(matching.pad_token_batch(
            log.queries[:min(512, log.n_queries)]))
        full_w = postings.shape[1]
        for frac in (0.125, 0.25, 0.5, 0.75, 1.0):
            w = max(1, int(full_w * frac))
            sub = jnp.asarray(postings[:, :w])
            matching.match_batch(sub, toks).block_until_ready()   # compile
            # min-of-reps: scheduling noise only ever ADDS time, so the
            # minimum is the cleanest estimate of the true service time
            dt = min(_timed(matching.match_batch, sub, toks)
                     for _ in range(10))
            words_l.append(w)
            us_l.append(1e6 * dt / int(toks.shape[0]))
    fit = cluster_pkg.fit_service_model(np.asarray(words_l),
                                        np.asarray(us_l))
    fit["scales"] = list(scales)
    fit["points"] = [{"words": int(w), "us_per_query": round(u, 3)}
                     for w, u in zip(words_l, us_l)]
    emit("cluster_calibration", fit["t_word_us"],
         f"t_fixed_us={fit['t_fixed_us']:.3f};"
         f"t_word_us={fit['t_word_us']:.4f};r2={fit['r2']:.4f};"
         f"points={fit['n_points']}")
    return fit


if __name__ == "__main__":
    import sys
    print("name,us_per_call,derived")
    from benchmarks import common
    common.begin_section("cluster", scale=CLUSTER_SCALE)
    run()
    for path in common.write_json():
        print(f"# wrote {path}", file=sys.stderr)
