#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload passage-q1.zipf-r80 \
        --seed 7 --seconds 30 --trace 0

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` traces the
window with JAX's profiler and prints its per-layer metrics. The last
line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown` when traced), then
`checks`, each number compared beside its limit; the same checks are the
last lines of standard error.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, and 1 when the cell cannot be run here. It never
falls back to the CPU.
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler trace under .bench_traces/")
    ap.add_argument("--control", action="store_true",
                    help="check the control's output in the program's "
                    "place: such a run has to come out not correct")
    args = ap.parse_args(argv)

    import bench
    try:
        cell = bench.load_cell(args.workload)
        import jax
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < cell.chips:
            print(f"[bench] needs {cell.chips} TPU chip(s); JAX reports "
                  f"{len(devs)} {devs[0].platform} device(s)",
                  file=sys.stderr)
            return 3
        result = bench.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START,
                                keep_trace=args.keep_trace,
                                control=args.control)
    except (bench.BenchError, OSError, KeyError, ImportError) as e:
        print(f"[bench] cannot run {args.workload}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
