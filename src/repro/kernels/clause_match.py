"""Pallas TPU kernel: batched packed clause-subset test (ψ^clause, eq. 8).

eligible[b] = ∃k . clause_k ⊆ query_b, over uint32-packed vocab bitsets.
One call classifies a whole serving batch — this replaces the engine's old
per-query host loop on the request path and is what the cluster router runs
once per batch before scatter-gathering to the tiers.

The subset test c ⊆ q is `(c & ~q) == 0` word-wise; a pure VPU op. Schedule:
  grid = (B/BB,); the clause axis is streamed INSIDE the kernel. The clause
  matrix stays in HBM (`memory_space=ANY`) and each [BK, Wv] block is
  double-buffered into VMEM with `make_async_copy`: while block j computes,
  block j+1 is already in flight on the second buffer slot, so the HBM read
  of the postings overlaps the VPU subset test instead of serializing ahead
  of it (the old grid-minor schedule paid the copy latency every step).
  The [BB, 1] eligibility accumulator lives in registers across the loop.
  VMEM: 2*BK*Wv*4 (clause slots) + BB*Wv*4 + the [BB, BK, Wv] mismatch
  intermediate — ≤ ~1.1 MB at the BB=BK=64, Wv=64 defaults, << 16 MB.
Zero-padded clause rows are the empty clause (⊆ everything), so padded K
rows are masked by their global index before the OR-reduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import block_dim


def _kernel(q_ref, c_hbm, o_ref, c_buf, sem, *,
            n_clauses: int, block_k: int, n_k: int):
    def copy_in(j, slot):
        return pltpu.make_async_copy(
            c_hbm.at[pl.ds(j * block_k, block_k), :],
            c_buf.at[slot],
            sem.at[slot],
        )

    copy_in(0, 0).start()
    q = q_ref[...]                                   # [BB, Wv] uint32

    def step(j, acc):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_k)
        def _prefetch():                             # next block, other slot
            copy_in(j + 1, jax.lax.rem(j + 1, 2)).start()

        copy_in(j, slot).wait()
        c = c_buf[slot]                              # [BK, Wv] uint32
        miss = c[None, :, :] & ~q[:, None, :]        # [BB, BK, Wv]
        sub = jnp.all(miss == 0, axis=-1)            # [BB, BK] bool
        # mask zero-padded clause rows (empty clause matches everything)
        k_global = jax.lax.broadcasted_iota(jnp.int32, sub.shape, 1) \
            + j * block_k
        sub = jnp.logical_and(sub, k_global < n_clauses)
        return acc | jnp.any(sub, axis=1, keepdims=True).astype(jnp.int32)

    init = jnp.zeros((q.shape[0], 1), jnp.int32)
    o_ref[...] = jax.lax.fori_loop(0, n_k, step, init)


@functools.partial(jax.jit, static_argnames=("block_b", "block_k", "interpret"))
def clause_match(
    query_bits: jnp.ndarray,   # uint32 [B, Wv]
    clause_bits: jnp.ndarray,  # uint32 [K, Wv]
    *,
    block_b: int = 64,
    block_k: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:              # bool [B]
    b, wv = query_bits.shape
    k, wk = clause_bits.shape
    assert wv == wk, (query_bits.shape, clause_bits.shape)
    bb, bp, nb = block_dim(b, block_b)
    bk, kp, nk = block_dim(k, block_k)
    if bp:
        query_bits = jnp.pad(query_bits, ((0, bp), (0, 0)))
    if kp:
        clause_bits = jnp.pad(clause_bits, ((0, kp), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, n_clauses=k, block_k=bk, n_k=nk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, wv), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),    # streamed by the kernel
        ],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b + bp, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((2, bk, wv), jnp.uint32),     # double-buffer slots
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(query_bits, clause_bits)
    return out[:b, 0].astype(bool)
