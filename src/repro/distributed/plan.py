"""Mesh-resident execution plan: one object that answers "where does this
op run?" for every kernel dispatch and every shard_map fusion in the repo.

Before this module, placement logic was scattered three ways:

  * `kernels/ops.py` carried a per-op `if pallas/interpret/xla` chain;
  * `core/optpes.py` and `core/sparse_step.py` each hand-rolled the same
    mesh-gating boilerplate (size check, dp-axes derivation, rank math,
    owner-local row gathers) in front of their shard_map bodies;
  * the cluster router had no device story at all — one host dispatch per
    shard.

`ExecutionPlan` binds the ambient `mesh_context` mesh, the `"shard"` axis
(solver partitions == fleet shards == mesh devices), and the resolved kernel
backend into a single immutable value. Everything placement-aware asks it:

    plan = current_plan()
    plan.placement("clause_match")   # "pallas" | "interpret" | "xla"
    plan.shard_fused                 # fuse over the "shard" axis?
    plan.model_fused                 # fuse over the "model" axis?

Backend resolution honours `REPRO_KERNEL_BACKEND`, either a single choice
("xla") or per-op placements ("xla,clause_match=interpret"); a bad value
raises `ValueError` naming the valid choices (it used to be a bare `assert`
that vanished under `python -O`).

`mesh_fused(body, ...)` is the single shard_map gate the solvers and the
cluster router share: it returns the bound shard-mapped callable when the
ambient (or given) mesh can fuse over the requested axis, else `None` so the
caller runs its direct path — no more copy-pasted `mesh.size == 1 or axis
not in mesh.axis_names` blocks. `axis_rank`/`owner_select`/`owner_row` are
the shared owner-local gather primitives those bodies were duplicating.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed import mesh_context

BACKENDS = ("pallas", "interpret", "xla")
SHARD_AXIS = "shard"



def shard_map(f, mesh, in_specs, out_specs, **kw):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


# -- backend resolution --------------------------------------------------------

def _check(b: str, source: str) -> str:
    if b not in BACKENDS:
        raise ValueError(
            f"invalid kernel backend {b!r} (from {source}); "
            f"valid choices: {', '.join(BACKENDS)} or 'auto'")
    return b


@functools.lru_cache(maxsize=8)
def _parse_placements(raw: str) -> tuple[str, dict[str, str]]:
    default, per_op = "auto", {}
    for entry in raw.split(","):
        entry = entry.strip()
        if not entry:
            continue
        if "=" in entry:
            op, _, b = entry.partition("=")
            b = b.strip()
            per_op[op.strip()] = b if b == "auto" else \
                _check(b, "REPRO_KERNEL_BACKEND")
        else:
            default = entry if entry == "auto" else \
                _check(entry, "REPRO_KERNEL_BACKEND")
    return default, per_op


def _env_placements() -> tuple[str, dict[str, str]]:
    """Parse REPRO_KERNEL_BACKEND: a default and/or per-op `op=backend`
    entries, comma-separated — e.g. "xla" or "xla,clause_match=interpret".
    Parsed once per distinct env value (this sits on the serving hot path)."""
    return _parse_placements(os.environ.get("REPRO_KERNEL_BACKEND", "auto"))


def resolve_backend(backend: str | None = None, op: str | None = None) -> str:
    """Resolve the execution path for one kernel call.

    Precedence: explicit `backend=` argument > per-op `REPRO_KERNEL_BACKEND`
    placement > its default entry > auto (pallas on TPU, xla elsewhere).
    """
    if backend is not None and backend != "auto":
        return _check(backend, "backend argument")
    default, per_op = _env_placements()
    b = per_op.get(op, default) if op is not None else default
    if b == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return b


# -- the plan ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Where ops run: the bound mesh, its role axes, the kernel backend.

    `shard_axis` is the fleet/partition axis (`"shard"`): when present with
    size > 1, the cluster router serves each batch as ONE shard_map program
    and `ops.partition_gain` computes each partition's gains on the device
    that owns it. `model_axis`/`data_axes` are the training-style roles the
    solver fusions (`optpes`, `sparse_step`) shard over.
    """
    mesh: Mesh
    backend: str
    shard_axis: str | None
    model_axis: str | None
    data_axes: tuple[str, ...]

    @property
    def n_shard_devices(self) -> int:
        return self.mesh.shape[self.shard_axis] if self.shard_axis else 1

    @property
    def shard_fused(self) -> bool:
        """Fuse fleet-facing ops over the `"shard"` axis?"""
        return self.shard_axis is not None and self.n_shard_devices > 1

    @property
    def model_fused(self) -> bool:
        """Fuse solver gain kernels over the `"model"` axis?"""
        return self.model_axis is not None and self.mesh.size > 1

    def placement(self, op: str, backend: str | None = None) -> str:
        """The execution path for `op` under this plan."""
        if backend is not None and backend != "auto":
            return _check(backend, "backend argument")
        _, per_op = _env_placements()
        b = per_op.get(op)
        if b == "auto":     # per-op auto: true auto-resolution, not default
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        return b if b is not None else self.backend

    def pinned(self, op: str, backend: str | None = None) -> bool:
        """True when `op`'s path is explicitly overridden (call argument or
        per-op env placement) — mesh fusions step aside so the pinned
        kernel implementation actually runs."""
        if backend is not None and backend != "auto":
            return True
        return op in _env_placements()[1]

    def tile_params(self, op: str, path: str, shape_bucket) -> dict:
        """Autotuned kernel kwargs for (op, path, shape-bucket) — the tile
        sibling of `placement`: placement picks WHICH impl runs, this picks
        HOW it tiles/decomposes. {} (impl defaults) on cache miss, when
        `shape_bucket` is None (untunable op), or when autotuning is disabled
        via REPRO_KERNEL_TILES=0."""
        if shape_bucket is None:
            return {}
        from repro.kernels import autotune  # leaf module; lazy to keep plan import-light
        return autotune.tile_params(op, path, shape_bucket)


def current_plan(backend: str | None = None) -> ExecutionPlan:
    """The plan the ambient `mesh_context` mesh implies."""
    mesh = mesh_context.current_mesh()
    names = mesh.axis_names
    return ExecutionPlan(
        mesh=mesh,
        backend=resolve_backend(backend),
        shard_axis=SHARD_AXIS if SHARD_AXIS in names else None,
        model_axis="model" if "model" in names else None,
        data_axes=tuple(a for a in names
                        if a not in ("model", SHARD_AXIS)),
    )


def shard_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D `("shard",)` mesh over (up to) `n_devices` local devices —
    what `use_mesh` wants for the fused cluster data plane."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(devs, (SHARD_AXIS,))


# -- shared shard_map fusion helpers ------------------------------------------

def mesh_fused(body, *, in_specs, out_specs, axis: str = "model",
               mesh: Mesh | None = None):
    """The one mesh gate: bind `body` over `mesh` (ambient by default), or
    return None when the mesh cannot fuse over `axis` — the caller then runs
    its direct single-device path. `check_vma` is off repo-wide: the packed
    uint32 operands and owner-select psums defeat vma inference.
    """
    mesh = mesh_context.current_mesh() if mesh is None else mesh
    if mesh.size == 1 or axis not in mesh.axis_names:
        return None
    return shard_map(body, mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def axis_rank(mesh: Mesh, axes) -> jnp.ndarray:
    """Row-major rank of the calling device over `axes` (shard_map body)."""
    rank = jnp.int32(0)
    for ax in axes:
        rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
    return rank


def owner_select(a: jnp.ndarray, idx: jnp.ndarray, rank: jnp.ndarray,
                 *, fill=0):
    """Owner-local rows `idx` (global indices) of a row-sharded local block.

    Inside a shard_map body: rows this device owns are sliced locally,
    out-of-range rows come back as `fill` — combine across owners with a
    psum (fill=0) or pmax (fill=-1 for padded id rows). Works for scalar or
    vector `idx`.
    """
    c_loc = a.shape[0]
    lidx = idx - rank * c_loc
    inb = (lidx >= 0) & (lidx < c_loc)
    rows = a[jnp.clip(lidx, 0, c_loc - 1)]
    keep = inb[..., None] if jnp.ndim(idx) else inb
    return jnp.where(keep, rows, jnp.full_like(rows, fill))


def owner_row(mat: jnp.ndarray, j: jnp.ndarray, *,
              w_axis: str | None = None, mesh: Mesh | None = None):
    """Row `j` of a dp-row-sharded matrix WITHOUT an all-gather.

    A traced-index gather on a sharded operand makes XLA all-gather the
    whole matrix (512 GB at solve_l scale — EXPERIMENTS §Perf); instead the
    owning dp-rank slices locally and a [W]-sized collective broadcasts the
    row. int32 matrices are treated as -1-padded id rows (combined via
    pmax); packed/float rows combine via psum. Falls back to `mat[j]` when
    the mesh can't fuse.
    """
    mesh = mesh_context.current_mesh() if mesh is None else mesh
    dp = tuple(a for a in mesh.axis_names if a != "model")
    is_ids = mat.dtype == jnp.int32

    def body(a, jj):
        row = owner_select(a, jj, axis_rank(mesh, dp),
                           fill=-1 if is_ids else 0)
        for ax in dp:
            row = jax.lax.pmax(row, ax) if is_ids else jax.lax.psum(row, ax)
        return row

    fused = mesh_fused(body, in_specs=(P(dp, w_axis), P()),
                       out_specs=P(w_axis), mesh=mesh)
    if fused is None:
        return mat[j]
    return fused(mat, j)
