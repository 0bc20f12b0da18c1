"""Benchmark entrypoint: one section per paper table/figure + kernel micro
+ streaming re-tiering + cluster serving + roofline summary. Prints
``name,us_per_call,derived`` CSV lines and writes machine-readable
``artifacts/bench/BENCH_<section>.json`` artifacts (one per section, each
stamped with the section's wall-clock ``seconds``) so the perf trajectory —
rows AND runtime — is recorded across PRs.

``--sections cluster,kernels`` runs a subset; ``--scale small`` overrides the
shared dataset scale. With no arguments the behavior (all sections, default
scale) is unchanged.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SECTIONS = ("kernels", "solvers", "parallel", "generalization", "stream",
            "cluster", "ingest", "frontend", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sections", default="",
                    help="comma-separated subset of: " + ",".join(SECTIONS)
                         + " (default: all)")
    ap.add_argument("--scale", default="",
                    help="dataset scale override (tiny/small/medium); "
                         "default: REPRO_BENCH_SCALE or 'small'")
    args = ap.parse_args()
    if args.scale:
        # before importing benchmark modules: they read the env at import
        os.environ["REPRO_BENCH_SCALE"] = args.scale
        os.environ["REPRO_BENCH_STREAM_SCALE"] = args.scale
        os.environ["REPRO_BENCH_CLUSTER_SCALE"] = args.scale
        os.environ["REPRO_BENCH_INGEST_SCALE"] = args.scale
        os.environ["REPRO_BENCH_FRONTEND_SCALE"] = args.scale
    selected = [s for s in args.sections.split(",") if s] or list(SECTIONS)
    unknown = set(selected) - set(SECTIONS)
    if unknown:
        ap.error(f"unknown sections {sorted(unknown)}; "
                 f"known: {','.join(SECTIONS)}")

    from benchmarks import common

    print("name,us_per_call,derived")
    from benchmarks import cluster, frontend, generalization, ingest, \
        kernels_micro, parallel_scaling, roofline, solvers, streaming

    def run_roofline() -> None:
        # roofline summary (only if dry-run artifacts exist)
        try:
            rows = roofline.run()
            common.emit("roofline_rows", len(rows),
                        "see artifacts/bench/BENCH_roofline.json")
        except Exception as e:  # noqa: BLE001
            common.emit("roofline_rows", 0, f"unavailable: {e}")

    runners = {
        "kernels": (kernels_micro.run, {}),
        "solvers": (solvers.run, {}),
        "parallel": (parallel_scaling.run, {}),
        "generalization": (generalization.run, {}),
        "stream": (streaming.run, {"scale": streaming.STREAM_SCALE}),
        "cluster": (cluster.run, {"scale": cluster.CLUSTER_SCALE}),
        "ingest": (ingest.run, {"scale": ingest.INGEST_SCALE}),
        "frontend": (frontend.run, {"scale": frontend.FRONTEND_SCALE}),
        "roofline": (run_roofline, {}),
    }
    try:
        for name in selected:
            fn, kw = runners[name]
            common.begin_section(name, **kw)
            fn()
    finally:
        # a failing section must not lose the sections already recorded
        for path in common.write_json():
            print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
