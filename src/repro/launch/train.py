"""Training launcher: `python -m repro.launch.train --arch <id> [...]`.

Runs a REDUCED config end-to-end on the host devices (this container is
CPU-only; the full configs are exercised by the dry-run). Demonstrates the
full production loop: mesh, sharded state, checkpoint/restart, straggler
policy, optional gradient compression.
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np


def synthetic_lm_batches(cfg, batch: int, seq: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    while True:
        toks = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int64)
        yield {"tokens": toks.astype(np.int32),
               "labels": toks.astype(np.int32)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    from repro.launch import compile_cache
    compile_cache.enable()

    from repro.configs import registry as R
    from repro.distributed import mesh_context
    from repro.distributed.compression import CompressionConfig
    from repro.launch import mesh as mesh_lib
    from repro.train.optimizer import OptimizerConfig
    from repro.train.trainer import DriverConfig, TrainingDriver, \
        make_train_step

    arch = R.get_arch(args.arch)
    cfg, smoke_batch, kind = arch.smoke()
    assert kind == "train", f"{args.arch} has no training smoke path"
    mesh = mesh_lib.make_host_mesh()

    with mesh, mesh_context.use_mesh(mesh):
        init_state, train_step = make_train_step(
            arch.loss_fn(cfg),
            OptimizerConfig(name=arch.optimizer, lr=args.lr,
                            warmup_steps=10, decay_steps=args.steps),
            compression=CompressionConfig(kind=args.compression))

        if arch.family == "lm":
            batches = synthetic_lm_batches(cfg, args.batch, args.seq)
        else:
            def repeat():
                while True:
                    yield smoke_batch
            batches = repeat()

        def params_init():
            if arch.family == "lm":
                from repro.models import transformer as T
                return T.init_params(jax.random.key(0), cfg)
            if arch.family == "gnn":
                from repro.models import egnn as G
                return G.init_params(jax.random.key(0), cfg)
            from repro.models import recsys as M
            init = {"deepfm": M.deepfm_init, "bst": M.bst_init,
                    "bert4rec": M.bert4rec_init,
                    "two-tower-retrieval": M.twotower_init}[args.arch]
            return init(jax.random.key(0), cfg)

        driver = TrainingDriver(init_state, train_step, DriverConfig(
            ckpt_dir=os.path.join(args.ckpt_dir, args.arch),
            ckpt_every=args.ckpt_every, max_steps=args.steps))
        state, history = driver.run(params_init, batches)

    print(f"[train] {args.arch}: {len(history)} steps this run, "
          f"loss {history[0]['loss']:.4f} -> {history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
