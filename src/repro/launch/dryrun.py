import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# ^ MUST precede any jax-importing import: jax locks device count on first init.

"""Multi-pod dry run (deliverable e).

For every (architecture x input-shape x mesh) cell: lower + compile the
step function on the production mesh with abstract (ShapeDtypeStruct)
operands, print/record memory_analysis() and cost_analysis(), and parse the
compiled HLO for collective traffic. Artifacts land in
artifacts/dryrun/<mesh>/<arch>__<shape>.json, which benchmarks/roofline.py
turns into EXPERIMENTS.md tables.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single --arch gemma2-2b
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both --arch all
"""
import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import numpy as np    # noqa: E402

from repro.configs import registry as R                    # noqa: E402
from repro.distributed import mesh_context, sharding       # noqa: E402
from repro.distributed.compression import CompressionConfig  # noqa: E402
from repro.launch import hlo_analysis, mesh as mesh_lib    # noqa: E402
from repro.train.optimizer import OptimizerConfig          # noqa: E402
from repro.train.trainer import make_train_step            # noqa: E402


def build_lowering(arch: R.ArchSpec, shape: str, mesh):
    cfg = arch.config_for(shape)
    cell = arch.cell_for(shape, mesh)
    named = lambda tree: sharding.named(mesh, tree)

    if cell.kind == "train":
        opt_cfg = OptimizerConfig(name=arch.optimizer)
        init_state, train_step = make_train_step(
            arch.loss_fn(cfg), opt_cfg, n_micro=cell.n_micro,
            compression=CompressionConfig(),
            grad_accum_dtype=arch.grad_accum_dtype)
        aparams = arch.abstract_params(cfg)
        astate = jax.eval_shape(init_state, aparams)
        pspecs = sharding.add_fsdp(arch.param_specs(cfg), aparams, mesh)
        state_sh = sharding.state_shardings(mesh, pspecs, astate)
        fn = train_step
        args = (astate, cell.inputs)
        in_sh = (state_sh, named(cell.input_specs))
    else:
        serve = arch.serve_fn(cfg, shape)
        aparams = arch.abstract_params(cfg)
        pspecs = sharding.add_fsdp(arch.param_specs(cfg), aparams, mesh)
        fn = serve
        args = (aparams, cell.inputs)
        in_sh = (named(pspecs), named(cell.input_specs))
    return fn, args, in_sh, cell


def run_cell(arch: R.ArchSpec, shape: str, mesh_name: str, out_dir: str,
             skip_existing: bool = False) -> dict:
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    path = os.path.join(out_dir, mesh_name, f"{arch.name}__{shape}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)

    record = {"arch": arch.name, "shape": shape, "mesh": mesh_name,
              "status": "ok"}
    if shape in arch.skips:
        record["status"] = "skipped"
        record["reason"] = arch.skips[shape]
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        print(f"[dryrun] SKIP {arch.name} x {shape} ({mesh_name}): "
              f"{arch.skips[shape][:60]}...")
        return record

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    try:
        with mesh, mesh_context.use_mesh(mesh):
            fn, args, in_sh, cell = build_lowering(arch, shape, mesh)
            # donate the train state / kv cache: updated-in-place on device
            donate = (0,) if cell.kind == "train" else \
                ((1,) if cell.kind == "decode" else ())
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            coll = hlo_analysis.collective_stats(hlo)
            probe = lm_cost_probe(arch, shape, mesh)

        record.update({
            "kind": cell.kind,
            "n_chips": n_chips,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops": float(cost.get("flops", -1.0)),
            "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
            "collectives": coll,
            "memory_analysis": _mem_dict(mem),
            "hlo_bytes": len(hlo),
            "probe": probe,
        })
        # per-device roofline inputs: cost_analysis on CPU reports the whole
        # (global) program; divide by chips downstream.
        print(f"[dryrun] OK   {arch.name} x {shape} ({mesh_name}) "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"GFLOPs={record['flops'] / 1e9:.1f} "
              f"coll={coll['total_bytes'] / 1e9:.2f}GB")
        print(f"         memory_analysis: {record['memory_analysis']}")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] FAIL {arch.name} x {shape} ({mesh_name}): "
              f"{record['error'][:200]}")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def lm_cost_probe(arch: R.ArchSpec, shape: str, mesh) -> dict | None:
    """XLA cost_analysis counts while-loop bodies ONCE, so scanned layers /
    microbatches / KV-chunks are undercounted by their trip counts. For LM
    cells we therefore lower scan-free probes at n_layers ∈ {1, 2} (chunked
    scans widened to a single chunk, one microbatch) and recover
      per_layer = cost(2L) - cost(1L);   fixed = cost(1L) - per_layer
      total ≈ n_micro * (fixed + n_layers * per_layer)
    Optimizer flops are O(params) — noise at these scales (documented)."""
    import dataclasses as dc
    if arch.family != "lm":
        return None
    cfg = arch.config_for(shape)
    cell = arch.cell_for(shape, mesh)
    n_micro = cell.n_micro
    probes = {}
    # decode probes: q_len=1 => single-chunk attention is exact and cheap.
    # train/prefill probes: keep real 4k KV chunking but UNROLLED (quadratic
    # score materialization at 32k would otherwise inflate the byte term).
    attn_chunk = 1 << 20 if cell.kind == "decode" else 4096
    for nl in (1, 2):
        pcfg = dc.replace(cfg, n_layers=nl, attn_chunk=attn_chunk,
                          attn_unroll=True, unroll_layers=True,
                          xent_chunk=1 << 20)
        parch = dc.replace(
            arch, config_for=lambda s, c=pcfg: c,
            cell_for=lambda s, m, c=pcfg: R.lm_cell(
                c, s, m, 1, batch_div=n_micro))
        fn, args, in_sh, _ = build_lowering(parch, shape, mesh)
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        cost = compiled.cost_analysis() or {}
        coll = hlo_analysis.collective_stats(compiled.as_text())
        probes[nl] = {"flops": float(cost.get("flops", 0.0)),
                      "bytes": float(cost.get("bytes accessed", 0.0)),
                      "coll": float(coll["total_bytes"])}
    out = {}
    for key in ("flops", "bytes", "coll"):
        per_layer = max(probes[2][key] - probes[1][key], 0.0)
        fixed = max(probes[1][key] - per_layer, 0.0)
        out[key] = n_micro * (fixed + cfg.n_layers * per_layer)
        out[f"{key}_per_layer"] = per_layer
    out["n_layers"] = cfg.n_layers
    out["n_micro"] = n_micro
    return out


def _mem_dict(mem) -> dict:
    if mem is None:
        return {"note": "memory_analysis unavailable on this backend"}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_per_device_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    return out or {"repr": str(mem)[:500]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()

    archs = R.all_archs()
    names = list(archs) if args.arch == "all" else args.arch.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for mesh_name in meshes:
        for name in names:
            arch = archs[name]
            shapes = arch.shapes if args.shape == "all" \
                else args.shape.split(",")
            for shape in shapes:
                results.append(run_cell(arch, shape, mesh_name, args.out,
                                        args.skip_existing))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"/ {len(results)} cells")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
