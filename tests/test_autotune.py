"""Tile autotuner: bucketing, cache resolution, search, and — the part that
matters — parity of autotuned tile/strategy picks through the ops dispatch
layer (a tuned entry must never change results, only speed)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset
from repro.distributed import plan as dplan
from repro.kernels import autotune, ops, ref


@pytest.fixture(autouse=True)
def _fresh_cache_state():
    autotune.invalidate()
    yield
    autotune.invalidate()


def test_pow2_bucketing_is_stable():
    assert autotune.bucket("clause_match", 512, 128, 64) == "b512_k128_w64"
    assert autotune.bucket("clause_match", 300, 100, 33) == "b512_k128_w64"
    assert autotune.bucket("bit_matvec", 4096, 512, 1) == "c4096_w512_r1"
    assert autotune.bucket("partition_gain", 4096, 512, 4) == "c4096_w512_p4"


def test_bucket_from_args_matches_bucket():
    q = jnp.zeros((300, 33), jnp.uint32)
    c = jnp.zeros((100, 33), jnp.uint32)
    assert autotune.bucket_from_args("clause_match", (q, c)) \
        == "b512_k128_w64"
    a = jnp.zeros((65, 9), jnp.uint32)
    x = jnp.zeros((9 * 32, 3), jnp.float32)
    assert autotune.bucket_from_args("bit_matvec", (a, x)) == "c128_w16_r4"
    assert autotune.bucket_from_args("sparse_gain", (a, x)) is None


def test_tile_params_miss_and_disable(tmp_path, monkeypatch):
    path = tmp_path / "tiles.json"
    path.write_text(json.dumps({
        "version": autotune.CACHE_VERSION, "backend": jax.default_backend(),
        "entries": {"clause_match|xla|b8_k8_w1":
                    {"strategy": "gemm", "_us": 12.0}}}))
    monkeypatch.setenv(autotune.ENV_VAR, str(path))
    autotune.invalidate()
    got = autotune.tile_params("clause_match", "xla", "b8_k8_w1")
    assert got == {"strategy": "gemm"}          # bookkeeping keys dropped
    assert autotune.tile_params("clause_match", "xla", "b16_k8_w1") == {}
    assert autotune.tile_params("clause_match", "interpret", "b8_k8_w1") == {}
    monkeypatch.setenv(autotune.ENV_VAR, "off")
    assert autotune.tile_params("clause_match", "xla", "b8_k8_w1") == {}


def test_cache_timed_on_another_backend_is_ignored(tmp_path, monkeypatch):
    """A gitignored cache tuned on one host never steers another backend:
    a TPU run keeps the tiles written in code, whatever a CPU run left."""
    entry = {"clause_match|xla|b8_k8_w1": {"strategy": "gemm"}}
    path = tmp_path / "tiles.json"
    monkeypatch.setenv(autotune.ENV_VAR, str(path))
    for recorded in ("tpu", None):
        blob = {"version": autotune.CACHE_VERSION, "entries": entry}
        if recorded:
            blob["backend"] = recorded
        path.write_text(json.dumps(blob))
        autotune.invalidate()
        assert autotune.tile_params("clause_match", "xla", "b8_k8_w1") == {}
    blob["backend"] = jax.default_backend()
    path.write_text(json.dumps(blob))
    autotune.invalidate()
    assert autotune.tile_params("clause_match", "xla", "b8_k8_w1") \
        == {"strategy": "gemm"}


def test_search_writes_picks_from_the_candidate_space(tmp_path):
    out = tmp_path / "tiles.json"
    blob = autotune.search(
        [("clause_match", "xla", (32, 8, 2)),
         ("bit_matvec", "xla", (64, 4, 1))],
        seed=0, reps=1, out=str(out))
    assert out.exists()
    entries = blob["entries"]
    assert set(entries) == {"clause_match|xla|b32_k8_w2",
                            "bit_matvec|xla|c64_w4_r1"}
    cm = {k: v for k, v in entries["clause_match|xla|b32_k8_w2"].items()
          if not k.startswith("_")}
    assert cm in autotune.SPACES[("clause_match", "xla")]
    # persisted file round-trips through the lookup path
    os.environ[autotune.ENV_VAR] = str(out)
    try:
        autotune.invalidate()
        assert autotune.tile_params("clause_match", "xla", "b32_k8_w2") == cm
    finally:
        del os.environ[autotune.ENV_VAR]


def test_ensure_cache_respects_disable(monkeypatch):
    monkeypatch.setenv(autotune.ENV_VAR, "0")
    path, n = autotune.ensure_cache()
    assert path == "<disabled>" and n == 0


def test_autotuned_picks_are_parity_exact(tmp_path, monkeypatch):
    """Dispatching through ops with a cache full of NON-default picks (odd
    strategies, odd blocks) must reproduce the reference bit-for-bit /
    allclose — the satellite acceptance for autotuned tile parity."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.integers(0, 2**32, (300, 33), dtype=np.uint32))
    cl = jnp.asarray(bitset.np_pack(rng.random((100, 33 * 32)) < 0.03))
    a = jnp.asarray(rng.integers(0, 2**32, (65, 9), dtype=np.uint32))
    x = jnp.asarray(rng.standard_normal((9 * 32, 3)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2**32, 9, dtype=np.uint32))
    bounds = (0, 3, 7, 9)
    entries = {
        "clause_match|xla|b512_k128_w64": {"strategy": "gemm"},
        "bit_matvec|xla|c128_w16_r4": {"strategy": "lut"},
        "clause_match|interpret|b512_k128_w64": {"block_b": 56, "block_k": 17},
        "bit_matvec|interpret|c128_w16_r4": {"block_c": 24, "block_w": 5},
        "coverage_gain|interpret|c128_w16": {"block_c": 24, "block_w": 5},
        "partition_gain|interpret|c128_w16_p4":
            {"block_c": 24, "block_w": 5},
    }
    path = tmp_path / "tiles.json"
    path.write_text(json.dumps(
        {"version": autotune.CACHE_VERSION, "backend": jax.default_backend(),
         "entries": entries}))
    monkeypatch.setenv(autotune.ENV_VAR, str(path))
    autotune.invalidate()

    plan = dplan.current_plan()
    assert plan.tile_params(
        "bit_matvec", "interpret",
        autotune.bucket_from_args("bit_matvec", (a, x))) \
        == {"block_c": 24, "block_w": 5}

    for backend in ("xla", "interpret"):
        np.testing.assert_array_equal(
            ops.clause_match(q, cl, backend=backend), ref.clause_match(q, cl))
        np.testing.assert_allclose(
            ops.bit_matvec(a, x, backend=backend), ref.bit_matvec(a, x),
            rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(
        ops.coverage_gain(a, mask, backend="interpret"),
        ref.coverage_gain(a, mask))
    np.testing.assert_array_equal(
        ops.partition_gain(a, mask, bounds, backend="interpret"),
        ops._partition_gain_xla(a, mask, bounds))
