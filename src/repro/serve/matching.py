"""Conjunctive matching over packed postings (paper §2.1, eq. 1).

m(q) = ∩_{v∈q} postings(v) — computed as an AND-reduce over packed doc
bitsets. Batched for serving: a [B, L]-padded token-id batch produces a
[B, Wd] packed match-set batch in one jitted call. Works against either the
full (Tier-2) postings or a Tier-1 sub-index produced by `tier_postings`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset


def _row(postings: jnp.ndarray, token: jnp.ndarray) -> jnp.ndarray:
    """One postings row as a dynamic slice; a padded slot reads row 0."""
    return jax.lax.dynamic_slice_in_dim(
        postings, jnp.maximum(token, 0), 1)[0]


@jax.jit
def match_batch(postings: jnp.ndarray,       # uint32 [V, W]
                tokens: jnp.ndarray,         # int32 [B, L], -1 padded
                ) -> jnp.ndarray:            # uint32 [B, W]
    """AND of postings rows per query; padded slots contribute all-ones.

    One term slot at a time, each query's row is fetched as its own
    dynamic slice (on the TPU a loop of row copies) and ANDed into a
    [B, W] accumulator. A plain gather of rows wider than 32,768 words
    would copy the whole postings first.
    """
    ones = jnp.uint32(0xFFFFFFFF)
    out = jnp.full((tokens.shape[0], postings.shape[1]), ones)
    for slot in range(tokens.shape[1]):
        rows = jax.vmap(_row, (None, 0))(postings, tokens[:, slot])
        out &= jnp.where(tokens[:, slot, None] >= 0, rows, ones)
    return out


def tier_postings(postings: np.ndarray, tier1_docs: np.ndarray) -> np.ndarray:
    """Restrict a postings matrix to Tier-1 documents.

    Production would re-index with a compacted doc-id space; for the
    measurement harness we keep global ids and mask, which preserves
    result-set semantics exactly.
    """
    t1 = bitset.np_pack(tier1_docs)
    return postings & t1[None, :]


def pad_token_batch(queries: list[tuple[int, ...]], pad_len: int | None = None) -> np.ndarray:
    l = pad_len or max((len(q) for q in queries), default=1)
    out = np.full((len(queries), l), -1, np.int32)
    for i, q in enumerate(queries):
        out[i, :len(q)] = list(q)[:l]
    return out


def pack_query_bits(queries: list[tuple[int, ...]], vocab_size: int) -> np.ndarray:
    """Token tuples -> packed vocab bitsets [B, Wv] (ψ^clause operand)."""
    qbits = np.zeros((len(queries), vocab_size), bool)
    for i, q in enumerate(queries):
        qbits[i, list(q)] = True
    return bitset.np_pack(qbits)


def classify_batch(clause_vocab_bits: np.ndarray,
                   queries: list[tuple[int, ...]], vocab_size: int,
                   *, backend: str | None = None) -> np.ndarray:
    """Batched ψ^clause (eq. 8) through the clause-subset-test kernel.

    One kernel call per serving batch; semantically identical to
    `ClauseTiering.classify_queries` (the per-query host reference).
    """
    from repro.kernels import ops
    if len(queries) == 0 or clause_vocab_bits.shape[0] == 0:
        return np.zeros(len(queries), bool)
    qbits = pack_query_bits(queries, vocab_size)
    return np.asarray(ops.clause_match(
        jnp.asarray(qbits), jnp.asarray(clause_vocab_bits), backend=backend))
