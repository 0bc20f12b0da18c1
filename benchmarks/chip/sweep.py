#!/usr/bin/env python3
"""Find the knee of an `open_loop` mix's configuration: the highest
offered rate at which the backlog does not grow across a window.

    python3 benchmarks/chip/sweep.py --mix zipf-r80-q1 \
        --rates 150,200,250,300 --seconds 10 --seed 1

One process sets up the configuration the mix names (`knee_of`) once,
warms every shape its batches can take (`max_batch`), then offers the
mix at each rate in turn. Per rate it prints the latency percentiles, the
mean latency of the first and second half of the window, and the lag of
the last answer behind the last arrival: a latency that grows from the
first half to the second is a growing backlog. The knee found is written
into the mix file by hand, as a fixed rate.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import bench
    import reference
    import serve_loop
    import traffic
    if jax.devices()[0].platform != "tpu":
        print("[sweep] no TPU", file=sys.stderr)
        return 3
    bench.enable_compile_cache()
    stalls = bench.Stalls()
    mix = bench.load_json(os.path.join(HERE, "mixes", args.mix + ".json"))
    max_batch = int(mix["max_batch"])
    path = os.path.join(HERE, "configs", mix["knee_of"] + ".json")
    cell = bench.Cell(name="sweep", chips=1,
                      config=bench.load_json(path), mix=mix,
                      end_to_end=[], per_layer=[])
    data, pipe, fleet = bench.setup(cell, args.seed)
    clauses = set(tuple(sorted(c)) for c in pipe.tiering().clauses)
    elig = [reference.eligible(q, clauses) for q in data.queries]
    serve_loop.warm_shapes(fleet, data.queries, elig, max_batch)
    bench.log(f"set-up {time.perf_counter() - T_START:.1f} s, "
              f"{stalls.n} compiles")
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        load = traffic.open_loop(dict(mix, rate_qps=rate),
                                 data.test_weights, args.seed, args.seconds)
        c0 = stalls.n
        win = serve_loop.run(fleet, data.queries, load.arrivals,
                             load.query_ids, max_batch, set(),
                             bench.Tracer.annotate)
        lat = win.latency_s * 1e3
        half = load.arrivals < args.seconds / 2
        row = {"rate": rate, "n": len(lat),
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p99_ms": float(np.percentile(lat, 99)),
               "first_half_mean_ms": float(lat[half].mean()),
               "second_half_mean_ms": float(lat[~half].mean()),
               "lag_s": win.elapsed_s - float(load.arrivals[-1]),
               "mean_batch": len(lat) / len(win.batches),
               "compiles": stalls.n - c0}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
