"""Kernel microbenchmarks: XLA path wall-time (CPU host) + the VMEM/HBM
traffic model for the TPU kernels (the quantity the Pallas tiling targets),
plus the per-kernel achieved-vs-roofline profile (`repro.obs.profile`) on
the host path AND the forced-4-device mesh path (fresh subprocess: XLA
fixes the device count at init)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def run(out_dir: str = "artifacts/bench") -> None:
    from repro.kernels import autotune, ops

    # Tune (or reuse) the tile/strategy cache first so every timed dispatch
    # below — and the profile rows the compare gate watches — runs the
    # measured-best variant, not the hardcoded defaults. Values are
    # machine-local (gitignored artifacts/); only the entry count is emitted.
    tiles_path, n_tiles = autotune.ensure_cache()
    emit("autotune_cache_entries", float(n_tiles), f"path={tiles_path}")

    rng = np.random.default_rng(0)

    for c, w in ((4096, 1024), (16384, 2048)):
        a = jnp.asarray(rng.integers(0, 2 ** 32, (c, w), dtype=np.uint32))
        x = jnp.asarray(rng.standard_normal((w * 32, 1)), jnp.float32)
        mask = jnp.asarray(rng.integers(0, 2 ** 32, w, dtype=np.uint32))
        dt = _time(lambda: ops.bit_matvec(a, x, backend="xla"))
        hbm_gb = (c * w * 4 + w * 32 * 4 + c * 4) / 1e9
        emit(f"kernel_bit_matvec_c{c}_w{w}", dt * 1e6,
             f"hbm_GB={hbm_gb:.3f};tpu_mem_bound_us={hbm_gb / 819 * 1e6:.1f}")
        dt = _time(lambda: ops.coverage_gain(a, mask, backend="xla"))
        emit(f"kernel_coverage_gain_c{c}_w{w}", dt * 1e6,
             f"hbm_GB={hbm_gb:.3f}")

    ids = jnp.asarray(rng.integers(0, 2 ** 20, (4096, 512)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2 ** 32, 2 ** 15, dtype=np.uint32))
    dt = _time(lambda: ops.sparse_gain(ids, mask, backend="xla"))
    emit("kernel_sparse_gain_c4096_m512", dt * 1e6,
         f"gather_GB={4096 * 512 * 4 / 1e9:.3f}")

    profile()
    profile_mesh()
    obs_overhead()


def _profile_body(reps: int = 5) -> list[dict]:
    """Drive clause_match / bit_matvec / partition_gain under the process
    profiler's measuring scope; returns `PROFILER.summary()` rows. Shapes
    are fixed, so words_scanned/bytes_moved are machine-independent (the
    regression gate compares them tightly); sync timing is wall-clock."""
    from repro import obs
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    c, w = 4096, 512
    a = jnp.asarray(rng.integers(0, 2 ** 32, (c, w), dtype=np.uint32))
    x = jnp.asarray(rng.standard_normal((w * 32, 1)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2 ** 32, w, dtype=np.uint32))
    q = jnp.asarray(rng.integers(0, 2 ** 32, (512, 64), dtype=np.uint32))
    cl = jnp.asarray(rng.integers(0, 2 ** 32, (128, 64), dtype=np.uint32))
    bounds = tuple(int(b) for b in np.linspace(0, w, 5).astype(int))

    prev = obs.set_enabled(True)
    try:
        # warm outside the measuring scope so compile time is never counted;
        # scoped() isolates this subsection's aggregation from anything an
        # earlier subsection (or the warmup itself) accrued in this process
        jax.block_until_ready(ops.clause_match(q, cl))
        jax.block_until_ready(ops.bit_matvec(a, x))
        jax.block_until_ready(ops.partition_gain(a, mask, bounds))
        with obs.PROFILER.scoped(), obs.PROFILER.measuring():
            for _ in range(reps):
                ops.clause_match(q, cl)
                ops.bit_matvec(a, x)
                ops.partition_gain(a, mask, bounds)
            return obs.PROFILER.summary()
    finally:
        obs.set_enabled(prev)


def profile() -> list[dict]:
    """Host-path roofline profile rows -> BENCH_kernels.json."""
    rows = _profile_body()
    for r in rows:
        emit(f"profile_host_{r['op']}", r["us_per_call"],
             f"path={r['path']};words_scanned={r['words_scanned']};"
             f"bytes_moved={r['bytes_moved']};"
             f"achieved_gbps={r['achieved_gbps']};"
             f"roofline_frac={r['roofline_frac']}", data=r)
    return rows


_MESH_PROFILE_PROBE = r"""
import json
import repro.distributed as D
from benchmarks import kernels_micro

with D.use_mesh(D.shard_mesh()):
    rows = kernels_micro._profile_body()
print(json.dumps(rows))
"""


def profile_mesh(ndev: int = 4) -> list[dict]:
    """The same profile inside a forced-`ndev`-device mesh subprocess —
    partition_gain resolves to the owner-local shard_map fusion there, so
    its rows land under path="mesh".

    CPU-only rehearsal: the probe forces `JAX_PLATFORMS=cpu` virtual
    devices, so these rows time XLA's CPU backend, never a chip."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               JAX_PLATFORMS="cpu", REPRO_OBS="1",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(root, "src"), root]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])))
    proc = subprocess.run([sys.executable, "-c", _MESH_PROFILE_PROBE],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    if proc.returncode != 0:
        emit("profile_mesh_error", 0.0,
             f"exit={proc.returncode}", data={"stderr": proc.stderr[-500:]})
        return []
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    for r in rows:
        emit(f"profile_mesh_{r['op']}", r["us_per_call"],
             f"path={r['path']};words_scanned={r['words_scanned']};"
             f"bytes_moved={r['bytes_moved']};"
             f"achieved_gbps={r['achieved_gbps']};"
             f"roofline_frac={r['roofline_frac']}", data=r)
    return rows


def obs_overhead(iters: int = 20) -> dict:
    """Disabled-telemetry tax on the serve hot path: `match_batch` bare vs
    wrapped in a (disabled) span + counter inc, exactly as `serve/engine.py`
    wraps it. The overhead must stay in the noise — the PR pins <5%."""
    from repro import obs
    from repro.serve import matching

    rng = np.random.default_rng(0)
    postings = jnp.asarray(
        rng.integers(0, 2 ** 32, (2048, 256), dtype=np.uint32))
    toks = jnp.asarray(rng.integers(0, 2048 * 32, (256, 8)), jnp.int32)
    ctr = obs.counter("bench_obs_overhead_total")

    def plain():
        return matching.match_batch(postings, toks)

    def wrapped():
        with obs.span("t1_match", n=int(toks.shape[0])) as sp:
            out = sp.sync(matching.match_batch(postings, toks))
        ctr.inc(int(toks.shape[0]))
        return out

    prev = obs.set_enabled(False)
    try:
        plain()                                   # compile once, shared
        t_plain = min(_time(plain, iters=iters) for _ in range(3))
        t_obs = min(_time(wrapped, iters=iters) for _ in range(3))
    finally:
        obs.set_enabled(prev)
    over = t_obs / t_plain - 1.0
    emit("kernel_obs_overhead_disabled", t_obs * 1e6,
         f"plain_us={t_plain * 1e6:.2f};overhead={over * 100:+.2f}%")
    return {"plain_us": t_plain * 1e6, "obs_us": t_obs * 1e6,
            "overhead": over}


if __name__ == "__main__":
    run()
