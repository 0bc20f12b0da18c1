"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts (unsupported casts,
blocks off the (8, 128) tiling, programs that overflow HBM), so each
main-path kernel is compiled here at the widths of the `passage` preset,
one chip's 2^21-document index, and the four-device fused serve program at
4 x 2^21 documents. Nothing runs: these tests prove compilation and
per-device memory, not results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

DOCS = 2 ** 21                 # one chip's passage shard
WD = DOCS // 32                # postings words per chip
V = 8192                       # passage vocabulary (head terms)
WV = V // 32                   # packed query / clause width
WQ = 8192 // 32                # packed unique-query width
C = 1024                       # mined clauses at min_support 1e-3
B, L = 512, 4                  # serve batch, padded query length
HBM = 16 * 2 ** 30             # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    from repro.kernels import bit_matvec, clause_match, coverage_gain, \
        partition_gain
    bounds = (0, WD // 4, WD // 2, 3 * WD // 4, WD)
    u32, f32 = jnp.uint32, jnp.float32
    return {
        "clause_match": (clause_match.clause_match,
                         [((B, WV), u32), ((C, WV), u32)]),
        "coverage_gain": (coverage_gain.coverage_gain,
                          [((C, WD), u32), ((WD,), u32)]),
        "bit_matvec": (bit_matvec.bit_matvec,
                       [((C, WQ), u32), ((WQ * 32, 1), f32)]),
        "partition_gain": (
            lambda a, m: partition_gain.partition_gain(a, m, bounds),
            [((C, WD), u32), ((WD,), u32)]),
    }


@pytest.mark.parametrize("op", ["clause_match", "coverage_gain",
                                "bit_matvec", "partition_gain"])
def test_main_path_kernel_compiles_for_v5e(one_chip, op):
    fn, shapes = _kernel_cases()[op]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_serve_fits_four_v5e_chips(topo, monkeypatch):
    """The four-device fused serve program at 4 x 2^21 documents: each
    device holds its own shard's stacked tiers, and the program's
    arguments, temporaries and output fit one chip's HBM."""
    from jax.sharding import Mesh
    from repro.cluster import mesh_serve
    # the plan resolves kernels from the attached backend (the CPU here):
    # pin the Pallas classify the chip runs
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    mesh = Mesh(topo.devices, ("shard",))
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("shard"))
    prog = mesh_serve._program(mesh, "shard", 4 * WD, WD, C)
    args = (_sds((B, WV), jnp.uint32, rep), _sds((C, WV), jnp.uint32, rep),
            _sds((B, L), jnp.int32, rep),
            _sds((4, 2, V, WD), jnp.uint32, shard),
            *[_sds((4,), jnp.int32, shard) for _ in range(3)])
    compiled = prog.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                  + mem.output_size_in_bytes)
    own = 2 * V * WD * 4                  # this device's (Tier-2, Tier-1)
    assert own <= mem.argument_size_in_bytes < 2 * own
    assert per_device < HBM, per_device
