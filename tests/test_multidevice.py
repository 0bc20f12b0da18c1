"""Multi-device semantics, run in a subprocess with 8 fake CPU devices
(the main test process must keep seeing 1 device).

Verifies: MoE expert-parallel == oracle on a real 2x4 mesh; row-sharded
embedding lookup == plain gather; quantized psum ~= exact psum; EGNN
edge-sharded message passing == single-device result; a reduced dry-run
cell lowers+compiles on the 8-device mesh.
"""
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.distributed import mesh_context
from repro.models import moe as M, embedding, egnn as G

AUTO = jax.sharding.AxisType.Auto
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AUTO, AUTO))
assert len(jax.devices()) == 8

# --- MoE EP on a real mesh vs oracle
cfg = M.MoEConfig(n_experts=8, top_k=2, d_expert=16, capacity_factor=4.0)
params = M.init_moe_params(jax.random.key(0), 8, cfg)
x = jax.random.normal(jax.random.key(1), (16, 8))
with mesh, mesh_context.use_mesh(mesh):
    y_ep, aux = jax.jit(lambda p, x: M.moe_apply(p, x, cfg))(params, x)
y_oracle = M.moe_apply_dense_oracle(params, x, cfg)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_oracle),
                           rtol=1e-5, atol=1e-5)
print("moe-ep-8dev OK")

# --- row-sharded embedding lookup
table = jax.random.normal(jax.random.key(2), (64, 4))
idx = jax.random.randint(jax.random.key(3), (16, 3), 0, 64)
with mesh, mesh_context.use_mesh(mesh):
    got = jax.jit(embedding.lookup)(table, idx)
np.testing.assert_allclose(np.asarray(got), np.asarray(table[idx]),
                           rtol=1e-6)
print("embedding-psum-8dev OK")

# --- quantized psum across 8 data shards
from repro.distributed.compression import quantized_psum
from repro.models.moe import shard_map
mesh1 = jax.make_mesh((8,), ("data",), axis_types=(AUTO,))
v = jax.random.normal(jax.random.key(4), (8, 32))
exact = v.sum(axis=0)
got = shard_map(lambda s: quantized_psum(s[0], "data"), mesh1,
                in_specs=(P("data"),), out_specs=P())(v)
err = float(jnp.abs(got - exact).max())
assert err < 8 * 2 * float(jnp.abs(v).max()) / 127, err
print("quantized-psum-8dev OK err=%.2e" % err)

# --- EGNN edge-sharded vs single-device
gcfg = G.EGNNConfig(n_layers=2, d_hidden=8, d_feat=4, n_classes=2)
gparams = G.init_params(jax.random.key(5), gcfg)
rng = np.random.default_rng(0)
batch = {
    "node_feat": jnp.asarray(rng.standard_normal((20, 4)), jnp.float32),
    "coords": jnp.asarray(rng.standard_normal((20, 3)), jnp.float32),
    "edges": jnp.asarray(rng.integers(0, 20, (2, 64)), jnp.int32),
}
h_ref, x_ref = G.forward(gparams, batch, gcfg)        # no mesh: local path
with mesh, mesh_context.use_mesh(mesh):
    h_sh, x_sh = jax.jit(lambda p, b: G.forward(p, b, gcfg))(gparams, batch)
np.testing.assert_allclose(np.asarray(h_ref), np.asarray(h_sh),
                           rtol=1e-4, atol=1e-5)
print("egnn-edge-shard-8dev OK")

# --- reduced dry-run lowering on the 8-device mesh
from repro.configs import registry as R
from repro.distributed import sharding
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import make_train_step
arch = R.get_arch("gemma2-2b")
scfg, sbatch, _ = arch.smoke()
init_state, train_step = make_train_step(
    arch.loss_fn(scfg), OptimizerConfig(name="adamw"))
aparams = jax.eval_shape(lambda: __import__("repro.models.transformer",
    fromlist=["x"]).init_params(jax.random.key(0), scfg))
astate = jax.eval_shape(init_state, aparams)
pspecs = sharding.add_fsdp(arch.param_specs(scfg), aparams, mesh,
                           min_size=64)
state_sh = sharding.state_shardings(mesh, pspecs, astate)
import jax.numpy as jnp2
batch_sds = {k: jax.ShapeDtypeStruct((16, 32), jnp2.int32)
             for k in ("tokens", "labels")}
batch_sh = {k: NamedSharding(mesh, P("data", None)) for k in batch_sds}
with mesh, mesh_context.use_mesh(mesh):
    compiled = jax.jit(train_step, in_shardings=(state_sh, batch_sh)) \
        .lower(astate, batch_sds).compile()
assert compiled.memory_analysis() is not None
print("dryrun-8dev OK")
print("ALL-MULTIDEVICE-OK")
"""


def test_multidevice_semantics():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root"}, cwd=".", timeout=900)
    assert "ALL-MULTIDEVICE-OK" in out.stdout, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
