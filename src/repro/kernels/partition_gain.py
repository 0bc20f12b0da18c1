"""Pallas TPU kernel: batched per-partition AND-NOT-popcount gains.

gains[c, k] = popcount(A[c, lo_k:hi_k] & ~covered[lo_k:hi_k]) — the
g_k(.|X) document-cost oracle of a partitioned knapsack (per-shard budgets
B_k over word-aligned doc ranges). One fused pass over the packed incidence
rows computes EVERY partition's cost-gain column at once: the AND-NOT
popcount runs on the VPU exactly like `coverage_gain`, and the word→partition
reduction is a popcount @ segment-one-hot matmul on the MXU, so arbitrary
(word-aligned) partition boundaries never break the `block_dim` tiling.

Counts are exact while n_docs < 2**24 (f32 integer accumulation); the
dispatch layer's XLA path (`ops.partition_gain`) is integer-exact at any
scale and is the semantics of record.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.tiles import block_dim

_LANE = 128          # f32 lane tile: pad the partition axis up to it


def _kernel(a_ref, m_ref, s_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]                       # [BC, BW] uint32
    m = m_ref[...]                       # [1, BW] uint32
    fresh = a & ~m
    # Mosaic has no uint32 -> f32 convert; a word's popcount fits int32
    cnt = jax.lax.population_count(fresh).astype(jnp.int32).astype(jnp.float32)
    # word -> partition segment reduction as one MXU matmul
    o_ref[...] += jnp.dot(cnt, s_ref[...],
                          preferred_element_type=jnp.float32)


def segment_selector(n_words: int, bounds: tuple[int, ...],
                     n_cols: int) -> jnp.ndarray:
    """f32 [n_words, n_cols] one-hot of each word's owning partition."""
    cuts = jnp.asarray(bounds[1:-1], jnp.int32)
    part = jnp.searchsorted(cuts, jnp.arange(n_words, dtype=jnp.int32),
                            side="right")
    return jax.nn.one_hot(part, n_cols, dtype=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bounds", "block_c", "block_w",
                                    "interpret"))
def partition_gain(
    a_bits: jnp.ndarray,      # uint32 [C, W]
    mask: jnp.ndarray,        # uint32 [W]
    bounds: tuple[int, ...],  # word offsets, len P+1, bounds[0]=0, [-1]=W
    *,
    block_c: int = 256,
    block_w: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:             # int32 [C, P]
    c, w = a_bits.shape
    p = len(bounds) - 1
    bc, cp, nc = block_dim(c, block_c)
    bw, wp, nw = block_dim(w, block_w)
    pp = -p % _LANE
    if cp or wp:
        # padded words carry zero incidence bits -> contribute 0 to any column
        a_bits = jnp.pad(a_bits, ((0, cp), (0, wp)))
        # np scalar, not a python int: 0xFFFFFFFF would be weak-typed int32
        # and overflow abstractification the first time a pad is non-empty
        mask = jnp.pad(mask, (0, wp), constant_values=np.uint32(0xFFFFFFFF))
    sel = segment_selector(w + wp, bounds, p + pp)
    grid = (nc, nw)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bw), lambda i, j: (i, j)),
            pl.BlockSpec((1, bw), lambda i, j: (0, j)),
            pl.BlockSpec((bw, p + pp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bc, p + pp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c + cp, p + pp), jnp.float32),
        interpret=interpret,
    )(a_bits, mask[None, :], sel)
    return out[:c, :p].astype(jnp.int32)
