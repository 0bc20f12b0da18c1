"""Mesh-resident data plane: the ExecutionPlan placement resolver, the
shared `distributed.mesh_fused` gate, and — in a subprocess with 4 fake CPU
devices (the main test process must keep seeing 1 device) — bit-identity of
the fused shard_map router serve vs the host scatter-gather path over
shards×replicas ∈ {1,2,4}², and of `partition_gain`'s owner-local path vs
the xla reference for uneven word partitions."""
import os
import subprocess
import sys

import numpy as np
import pytest


# -- backend resolution (the old bare-assert bug) -----------------------------

def test_resolve_backend_rejects_bad_argument():
    from repro.kernels import ops
    with pytest.raises(ValueError, match="pallas, interpret, xla"):
        ops.resolve_backend("cuda")


def test_resolve_backend_rejects_bad_env(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
    with pytest.raises(ValueError, match="REPRO_KERNEL_BACKEND"):
        ops.resolve_backend()
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla,clause_match=nope")
    with pytest.raises(ValueError, match="valid choices"):
        ops.resolve_backend()


def test_resolve_backend_accepts_valid_choices(monkeypatch):
    from repro import distributed
    for b in ("pallas", "interpret", "xla"):
        assert distributed.resolve_backend(b) == b
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert distributed.resolve_backend() == "interpret"
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    assert distributed.resolve_backend() in ("pallas", "xla")   # auto


def test_per_op_placement(monkeypatch):
    """REPRO_KERNEL_BACKEND can pin individual ops to a path."""
    from repro import distributed
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla,clause_match=interpret")
    plan = distributed.current_plan()
    assert plan.placement("clause_match") == "interpret"
    assert plan.placement("bit_matvec") == "xla"
    # an explicit per-call backend beats the env placement
    assert plan.placement("clause_match", "xla") == "xla"
    assert plan.pinned("clause_match") and not plan.pinned("bit_matvec")
    # a per-op "auto" restores auto-resolution (xla on CPU), not the default
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret,bit_matvec=auto")
    plan = distributed.current_plan()
    assert plan.placement("bit_matvec") == "xla"
    assert plan.placement("clause_match") == "interpret"


# -- the plan on the default (1-device) mesh ----------------------------------

def test_current_plan_single_device_defaults():
    from repro import distributed
    plan = distributed.current_plan()
    assert plan.shard_axis is None and not plan.shard_fused
    assert not plan.model_fused
    assert plan.n_shard_devices == 1


def test_mesh_fused_gates_off_mesh():
    """On a 1-device mesh every fusion gate returns None (direct path)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import distributed
    assert distributed.mesh_fused(lambda x: x, in_specs=(P(),),
                                  out_specs=P()) is None
    with distributed.use_mesh(distributed.shard_mesh(1)):
        plan = distributed.current_plan()
        assert plan.shard_axis == "shard" and not plan.shard_fused
        assert distributed.mesh_fused(lambda x: x, in_specs=(P(),),
                                      out_specs=P(), axis="shard") is None
    del jax


def test_owner_row_identity_off_mesh():
    import jax.numpy as jnp
    from repro import distributed
    mat = jnp.arange(12, dtype=jnp.uint32).reshape(4, 3)
    np.testing.assert_array_equal(
        np.asarray(distributed.owner_row(mat, jnp.int32(2))),
        np.asarray(mat[2]))


def test_serve_host_path_on_one_device_shard_mesh(tiny_data):
    """A size-1 "shard" mesh must leave serving on the (host) direct path
    and stay oracle-exact — plain CPU runs are unchanged by the plan layer."""
    from repro import api, distributed
    pipe = api.TieringPipeline.from_data(tiny_data).solve(
        "greedy", budget_frac=0.5)
    with distributed.use_mesh(distributed.shard_mesh(1)):
        fleet = pipe.deploy_cluster(n_shards=2, t1_replicas=2)
        got = fleet.serve(tiny_data.log.queries[:64])
    want = fleet.serve_reference(tiny_data.log.queries[:64])
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert not fleet.router._mesh_tables        # fused path never engaged


# -- 4-device parity, in a subprocess -----------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np, jax.numpy as jnp
from repro import api, distributed as D
from repro.kernels import ops

assert len(jax.devices()) == 4

# --- partition_gain: owner-local path == xla reference, uneven partitions
rng = np.random.default_rng(0)
a = jnp.asarray(rng.integers(0, 2**32, (37, 13), dtype=np.uint32))
m = jnp.asarray(rng.integers(0, 2**32, (13,), dtype=np.uint32))
for bounds in [(0, 3, 4, 9, 13), (0, 13), (0, 1, 2, 3, 4, 5, 6, 13)]:
    ref = ops._partition_gain_xla(a, m, bounds)
    with D.use_mesh(D.shard_mesh()):
        got = ops.partition_gain(a, m, bounds)
        jitted = jax.jit(lambda a, m, b=bounds: ops.partition_gain(a, m, b))(
            a, m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(jitted), np.asarray(ref))
# a pinned path steps around the mesh fusion (and still agrees)
with D.use_mesh(D.shard_mesh()):
    pinned = ops.partition_gain(a, m, (0, 3, 4, 9, 13), backend="xla")
np.testing.assert_array_equal(
    np.asarray(pinned), np.asarray(ops._partition_gain_xla(a, m,
                                                           (0, 3, 4, 9, 13))))
print("partition-gain-owner-local OK")

# --- fused shard_map serve == host scatter-gather, shards x replicas {1,2,4}^2
pipe = (api.TieringPipeline.from_synthetic(seed=0, scale="tiny")
        .mine(min_support=1e-3).solve("greedy", budget_frac=0.5))
queries = pipe.log.queries[:192]


def snap(fleet):
    s = fleet.stats
    return (s.n_tier1, s.tier1_words, s.tier2_words,
            [(t.psi_generation, t.t1_generations, t.n_tier1, t.n_tier2,
              t.t1_shards, t.t1_contents, t.expected_contents)
             for t in fleet.trace])


for n_shards in (1, 2, 4):
    for reps in (1, 2, 4):
        host_fleet = pipe.deploy_cluster(n_shards=n_shards, t1_replicas=reps,
                                         t2_replicas=reps)
        host = []
        for s in range(0, len(queries), 64):
            host.extend(host_fleet.serve(queries[s:s + 64]))
        mesh_fleet = pipe.deploy_cluster(n_shards=n_shards, t1_replicas=reps,
                                         t2_replicas=reps)
        with D.use_mesh(D.shard_mesh()):
            mesh = []
            for s in range(0, len(queries), 64):
                mesh.extend(mesh_fleet.serve(queries[s:s + 64]))
        for a, b in zip(host, mesh):
            np.testing.assert_array_equal(a, b)
        assert snap(host_fleet) == snap(mesh_fleet), (n_shards, reps)
        assert mesh_fleet.consistency_ok()
        assert mesh_fleet.router._mesh_tables, "fused path never engaged"
print("fused-serve-parity-9combos OK")

# --- mid-roll parity incl. the Tier-2-only fallback gap, fused end to end
from repro import cluster
from repro.core import SOLVERS
from repro.core.tiering import ClauseTiering
data = pipe.data
r2 = SOLVERS["greedy"](pipe.problem, int(data.n_docs * 0.25))
t_new = ClauseTiering.from_selection(data, r2.selected)
with D.use_mesh(D.shard_mesh()):
    fleet = cluster.TieredCluster(data.postings, pipe.tiering(), data.n_docs,
                                  n_shards=2, t1_replicas=1)
    fleet.serve(queries[:64])
    fleet.swap_tiering(t_new)
    fallback = batches = 0
    while fleet.router.rollout is not None and batches < 64:
        got = fleet.serve(queries[:64])
        want = fleet.serve_reference(queries[:64])
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
        fallback += fleet.trace[-1].psi_generation == -1
        batches += 1
    assert fallback > 0, "expected a Tier-2 fallback window"
    assert fleet.consistency_ok()
print("fused-rolling-swap OK")

# --- partitioned solves are bit-identical under the shard mesh
cold = api.TieringPipeline.from_data(data).solve(
    "greedy", budget_frac=0.5, budget_split="traffic", n_shards=4)
with D.use_mesh(D.shard_mesh()):
    fused = api.TieringPipeline.from_data(data).solve(
        "greedy", budget_frac=0.5, budget_split="traffic", n_shards=4)
assert cold.result.order == fused.result.order
np.testing.assert_array_equal(np.asarray(cold.result.extra["g_part"]),
                              np.asarray(fused.result.extra["g_part"]))
print("partitioned-solve-identity OK")

# --- residency: each shard's slices and route-table block on its own device,
# and the full-width oracle never placed unless serve_reference asks for it
devs = jax.devices()
with D.use_mesh(D.shard_mesh()):
    fleet = pipe.deploy_cluster(n_shards=4, t1_replicas=2)
    fleet.serve(queries[:64])
for groups in (fleet.router.t1, fleet.router.t2):
    for s, group in enumerate(groups):
        for rep in group:
            assert rep.postings.devices() == {devs[s]}, (s, rep)
(table,) = fleet.router._mesh_tables.values()
for sh in table.tiers.addressable_shards:
    assert sh.device == devs[sh.index[0].start], sh
assert fleet._oracle is None
print("per-device-residency OK")
print("ALL-MESH-OK")
"""


def test_mesh_parity_4dev():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": os.environ.get(
            "PATH", "/usr/bin:/bin"), "HOME": os.environ.get("HOME", "/root")},
        cwd=os.path.join(os.path.dirname(__file__), ".."), timeout=900)
    assert "ALL-MESH-OK" in out.stdout, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
