"""Seeded, deterministic tile/strategy autotuner for the packed-bit kernels.

Every kernel dispatch in `ops.py` resolves its tuning parameters through
`ExecutionPlan.tile_params`, which lands here: the call shape is rounded to a
power-of-two bucket (`tiles.pow2_bucket`) and looked up in a persisted JSON
cache keyed ``"{op}|{path}|{bucket}"``.  A hit overrides the hardcoded
defaults (block sizes for the Pallas/interpret kernels, algorithm strategy +
chunking for the XLA host fallbacks); a miss keeps the status-quo defaults, so
the cache is a pure go-faster overlay and never a correctness dependency.

Cache resolution order:

- ``REPRO_KERNEL_TILES=0|off|none``  → autotuning disabled, defaults only.
- ``REPRO_KERNEL_TILES=/path.json``  → explicit cache file.
- unset                              → ``artifacts/autotune/tiles.json``.

A cache applies only on the backend that timed it (its recorded
``"backend"``): the file is gitignored and tuned per host, so a leftover
CPU-timed copy never changes which tiles a chip runs — there the kernels
keep the defaults written in code.

The search itself (`search` / `ensure_cache`, also exposed as
``python -m repro.kernels.autotune``) is deterministic by construction: data
is synthesized from a fixed seed, candidates are enumerated in a fixed order,
timing uses interleaved round-robin trials with a median reduce (robust to
wall-clock drift on shared hosts), and ties break toward the earlier
candidate.  The *picked* entries are machine-dependent by design — that is the
point of tuning — which is why the cache lives under the gitignored
``artifacts/`` tree and is regenerated per host, never committed.
"""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Any, Callable, Dict, List, Sequence, Tuple

from repro.kernels.tiles import pow2_bucket

ENV_VAR = "REPRO_KERNEL_TILES"
DEFAULT_CACHE = os.path.join("artifacts", "autotune", "tiles.json")
_DISABLED = ("0", "off", "none", "false")
CACHE_VERSION = 1

# ---------------------------------------------------------------------------
# Candidate spaces.
#
# Keyed (op, path).  Pallas/interpret entries sweep block shapes; the XLA host
# path sweeps *algorithm strategies* (the block structure there is XLA's
# business, but the decomposition — scan-chunked unpack+GEMM vs. 32-way
# shift-mask unroll vs. byte-LUT gather — changes the memory traffic shape and
# the winner flips with (C, W, R)).  Every candidate is integer-exact; only
# speed differs.
# ---------------------------------------------------------------------------

_BLOCKS_CM = [
    {"block_b": bb, "block_k": bk} for bb in (32, 64, 128) for bk in (32, 64, 128)
]
_BLOCKS_CW = [
    {"block_c": bc, "block_w": bw} for bc in (64, 128, 256) for bw in (64, 128, 256)
]
_BLOCKS_CW_WIDE = [
    {"block_c": bc, "block_w": bw} for bc in (128, 256) for bw in (128, 256, 512)
]

SPACES: Dict[Tuple[str, str], List[Dict[str, Any]]] = {
    ("clause_match", "xla"): [
        {"strategy": "plain"},
        {"strategy": "scan", "chunk_b": 256},
        {"strategy": "scan", "chunk_b": 512},
        {"strategy": "scan", "chunk_b": 1024},
        {"strategy": "gemm"},
    ],
    ("bit_matvec", "xla"): [
        {"strategy": "scan", "chunk_w": 128},
        {"strategy": "scan", "chunk_w": 256},
        {"strategy": "scan", "chunk_w": 512},
        {"strategy": "unroll"},
        {"strategy": "lut"},
    ],
    ("clause_match", "pallas"): _BLOCKS_CM,
    ("clause_match", "interpret"): _BLOCKS_CM,
    ("bit_matvec", "pallas"): _BLOCKS_CW,
    ("bit_matvec", "interpret"): _BLOCKS_CW,
    ("coverage_gain", "pallas"): _BLOCKS_CW_WIDE,
    ("coverage_gain", "interpret"): _BLOCKS_CW_WIDE,
    ("partition_gain", "pallas"): _BLOCKS_CW_WIDE,
    ("partition_gain", "interpret"): _BLOCKS_CW_WIDE,
}


def bucket(op: str, *dims: int) -> str:
    """Canonical bucket string for an op's characteristic dims (pow2-rounded)."""
    names = {
        "clause_match": ("b", "k", "w"),
        "bit_matvec": ("c", "w", "r"),
        "coverage_gain": ("c", "w"),
        "partition_gain": ("c", "w", "p"),
        "fused_match": ("b", "l", "w"),
    }[op]
    return "_".join(f"{n}{pow2_bucket(max(1, d))}" for n, d in zip(names, dims))


def bucket_from_args(op: str, args: Sequence[Any]):
    """Derive the shape bucket from the positional args `ops._run` sees.

    Returns None for ops with no tunable space (dispatch then skips the cache
    lookup entirely, keeping the hot path at two dict probes).
    """
    if op == "clause_match":
        q, c = args[0], args[1]
        return bucket(op, q.shape[0], c.shape[0], q.shape[1])
    if op == "bit_matvec":
        a, x = args[0], args[1]
        r = x.shape[1] if x.ndim > 1 else 1
        return bucket(op, a.shape[0], a.shape[1], r)
    if op == "coverage_gain":
        a = args[0]
        return bucket(op, a.shape[0], a.shape[1])
    return None


# ---------------------------------------------------------------------------
# Cache lookup (hot path — memoized on the env value so a test flipping
# REPRO_KERNEL_TILES via monkeypatch invalidates naturally; call
# `invalidate()` after rewriting the cache file in-place).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _load_entries(path: str, backend: str) -> Dict[str, Dict[str, Any]]:
    try:
        with open(path) as fh:
            blob = json.load(fh)
    except (OSError, ValueError):
        return {}
    if not isinstance(blob, dict) or blob.get("version") != CACHE_VERSION \
            or blob.get("backend") != backend:
        return {}
    entries = blob.get("entries", {})
    return entries if isinstance(entries, dict) else {}


@functools.lru_cache(maxsize=4096)
def _tile_params_cached(env_raw, backend: str, op: str, path: str,
                        shape_bucket: str):
    if env_raw is not None and env_raw.strip().lower() in _DISABLED:
        return {}
    cache_path = env_raw if env_raw else DEFAULT_CACHE
    got = _load_entries(cache_path, backend).get(
        f"{op}|{path}|{shape_bucket}")
    if not isinstance(got, dict):
        return {}
    # Drop bookkeeping keys; whatever remains is kwargs for the kernel impl.
    return {k: v for k, v in got.items() if not k.startswith("_")}


def tile_params(op: str, path: str, shape_bucket) -> Dict[str, Any]:
    """Tuned kwargs for (op, path, bucket); {} on miss or when disabled."""
    if shape_bucket is None:
        return {}
    import jax
    return dict(_tile_params_cached(os.environ.get(ENV_VAR),
                                    jax.default_backend(), op, path,
                                    shape_bucket))


def invalidate() -> None:
    """Drop memoized cache state (tests rewrite tiles.json in place)."""
    _load_entries.cache_clear()
    _tile_params_cached.cache_clear()


def cache_path() -> str:
    raw = os.environ.get(ENV_VAR)
    if raw and raw.strip().lower() not in _DISABLED:
        return raw
    return DEFAULT_CACHE


# ---------------------------------------------------------------------------
# Search.
# ---------------------------------------------------------------------------

# Default tuning workload: the shapes the checked-in benchmarks exercise, so a
# fresh cache immediately feeds the profile/micro rows.  (op, path, dims).
DEFAULT_WORKLOAD: List[Tuple[str, str, Tuple[int, ...]]] = [
    ("clause_match", "xla", (512, 128, 64)),
    ("clause_match", "xla", (2048, 512, 64)),
    ("bit_matvec", "xla", (4096, 512, 1)),
    ("bit_matvec", "xla", (4096, 1024, 1)),
]


def _synth(op: str, dims: Tuple[int, ...], seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    if op == "clause_match":
        b, k, wv = dims
        q = rng.integers(0, 1 << 32, size=(b, wv), dtype=np.uint32)
        c = (
            rng.integers(0, 1 << 32, size=(k, wv), dtype=np.uint32)
            & rng.integers(0, 1 << 32, size=(k, wv), dtype=np.uint32)
            & rng.integers(0, 1 << 32, size=(k, wv), dtype=np.uint32)
        )
        hits = max(1, min(b, k) // 4)  # force some real subset matches
        c[:hits] &= q[:hits]
        return (q, c)
    if op == "bit_matvec":
        c, w, r = dims
        a = rng.integers(0, 1 << 32, size=(c, w), dtype=np.uint32)
        x = rng.standard_normal((w * 32, r), dtype=np.float32)
        return (a, x)
    if op == "coverage_gain":
        c, w = dims
        a = rng.integers(0, 1 << 32, size=(c, w), dtype=np.uint32)
        m = rng.integers(0, 1 << 32, size=(w,), dtype=np.uint32)
        return (a, m)
    if op == "partition_gain":
        c, w, p = dims
        a = rng.integers(0, 1 << 32, size=(c, w), dtype=np.uint32)
        m = rng.integers(0, 1 << 32, size=(w,), dtype=np.uint32)
        bounds = tuple(int(v) for v in np.linspace(0, c, p + 1).astype(int))
        return (a, m, bounds)
    raise ValueError(f"no synthetic workload for op {op!r}")


def _impl_call(op: str, path: str, args, params: Dict[str, Any]) -> Callable[[], Any]:
    from repro.kernels import ops as _ops

    fn = _ops._IMPLS[op][path]
    return lambda: fn(*args, **params)


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def search(
    workload: Sequence[Tuple[str, str, Tuple[int, ...]]] | None = None,
    *,
    seed: int = 0,
    reps: int = 3,
    out: str | None = None,
    verbose: bool = False,
) -> Dict[str, Any]:
    """Measure every candidate for every workload entry and persist the picks.

    Timing is interleaved round-robin (candidate 0 rep 0, candidate 1 rep 0,
    ..., candidate 0 rep 1, ...) with a median reduce so slow drift on a busy
    host biases all candidates equally instead of whichever ran last.
    """
    import jax
    import numpy as np

    workload = list(workload if workload is not None else DEFAULT_WORKLOAD)
    entries: Dict[str, Dict[str, Any]] = {}
    for op, path, dims in workload:
        space = SPACES.get((op, path))
        if not space:
            continue
        host_args = _synth(op, dims, seed)
        args = tuple(
            jax.numpy.asarray(a) if isinstance(a, np.ndarray) else a for a in host_args
        )
        calls = [_impl_call(op, path, args, params) for params in space]
        # Warm (compile) every candidate before any timed trial.
        baseline = None
        for call in calls:
            got = jax.block_until_ready(call())
            if baseline is None:
                baseline = got
            else:
                # Tuning must never trade exactness for speed.
                # float candidates reassociate sums (lut/unroll vs scan), so
                # tolerance, not bit-equality; integer ops compare exactly
                ok = jax.numpy.allclose(
                    jax.numpy.asarray(got, jax.numpy.float32),
                    jax.numpy.asarray(baseline, jax.numpy.float32),
                    rtol=1e-4, atol=1e-3,
                )
                if not bool(ok):  # pragma: no cover - guards impl bugs
                    raise AssertionError(f"autotune candidate mismatch for {op}/{path}")
        times: List[List[float]] = [[] for _ in calls]
        for _ in range(reps):
            for idx, call in enumerate(calls):
                t0 = time.perf_counter()
                jax.block_until_ready(call())
                times[idx].append(time.perf_counter() - t0)
        med = [_median(t) for t in times]
        best = min(range(len(space)), key=lambda i: (med[i], i))
        key = f"{op}|{path}|{bucket(op, *dims)}"
        entries[key] = dict(space[best])
        entries[key]["_us"] = round(med[best] * 1e6, 1)
        if verbose:
            print(f"{key}: {space[best]} ({med[best] * 1e6:.0f} us)")
    blob = {
        "version": CACHE_VERSION,
        "seed": seed,
        "backend": jax.default_backend(),
        "entries": dict(sorted(entries.items())),
    }
    dest = out if out is not None else cache_path()
    os.makedirs(os.path.dirname(dest) or ".", exist_ok=True)
    with open(dest, "w") as fh:
        json.dump(blob, fh, indent=2, sort_keys=True)
        fh.write("\n")
    invalidate()
    return blob


def ensure_cache(*, seed: int = 0) -> Tuple[str, int]:
    """Create the default-workload cache if the resolved path has none.

    Returns (path, n_entries).  No-op (path, 0 entries counted from disk) when
    tuning is disabled via the env switch.
    """
    raw = os.environ.get(ENV_VAR)
    if raw is not None and raw.strip().lower() in _DISABLED:
        return ("<disabled>", 0)
    import jax
    path = cache_path()
    entries = _load_entries(path, jax.default_backend())
    if entries:
        return (path, len(entries))
    blob = search(seed=seed, out=path)
    return (path, len(blob["entries"]))


def main(argv: Sequence[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="regenerate the kernel tile cache")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=None, help=f"cache path (default {DEFAULT_CACHE})")
    ns = ap.parse_args(argv)
    blob = search(seed=ns.seed, reps=ns.reps, out=ns.out, verbose=True)
    dest = ns.out if ns.out is not None else cache_path()
    print(f"wrote {len(blob['entries'])} entries -> {dest}")
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
