"""Packed-uint32 bitset algebra.

The whole SCSK engine works over packed bitsets: coverage masks over queries
and documents, and clause->query / clause->doc incidence matrices. Packing is
32x denser than bool arrays and `lax.population_count` makes AND-NOT-popcount
the cheapest possible marginal-gain primitive on TPU VPUs.

Conventions:
  * a bitset over a universe of size n is a uint32 array [..., W] with
    W = ceil(n / 32); bit i lives in word i >> 5 at position i & 31
    (little-endian within the word).
  * padding bits (>= n) are always zero; every producer below guarantees it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32


def n_words(n_bits: int) -> int:
    return (n_bits + WORD - 1) // WORD


# ---------------------------------------------------------------------------
# numpy (host / preprocessing) side
# ---------------------------------------------------------------------------

def np_pack(bits: np.ndarray) -> np.ndarray:
    """Pack a bool array [..., n] into uint32 words [..., ceil(n/32)]."""
    bits = np.asarray(bits, dtype=bool)
    n = bits.shape[-1]
    w = n_words(n)
    padded = np.zeros(bits.shape[:-1] + (w * WORD,), dtype=bool)
    padded[..., :n] = bits
    padded = padded.reshape(bits.shape[:-1] + (w, WORD))
    weights = (np.uint32(1) << np.arange(WORD, dtype=np.uint32))
    return (padded.astype(np.uint32) * weights).sum(axis=-1, dtype=np.uint32)


def np_pack_pairs(rows: np.ndarray, cols: np.ndarray, n_rows: int,
                  n_bits: int) -> np.ndarray:
    """uint32 [n_rows, ceil(n_bits/32)] with bit `cols[i]` of row `rows[i]`
    set — `np_pack` of the sparse bool matrix, never materialising it."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    w = n_words(n_bits)
    out = np.zeros(n_rows * w, np.uint32)
    np.bitwise_or.at(out, rows * w + (cols >> 5),
                     np.uint32(1) << (cols & 31).astype(np.uint32))
    return out.reshape(n_rows, w)


def np_pack_sets(sets, n_bits: int, *, transpose: bool = False) -> np.ndarray:
    """Packed rows [len(sets), ceil(n_bits/32)] from id collections; with
    `transpose`, the inverted index [n_bits, ceil(len(sets)/32)] whose row
    v has bit i set iff v ∈ sets[i]."""
    lengths = [len(s) for s in sets]
    idx = np.repeat(np.arange(len(sets)), lengths)
    ids = np.fromiter((int(v) for s in sets for v in s), np.int64,
                      count=sum(lengths))
    if transpose:
        return np_pack_pairs(ids, idx, n_bits, len(sets))
    return np_pack_pairs(idx, ids, len(sets), n_bits)


def np_unpack(words: np.ndarray, n_bits: int) -> np.ndarray:
    """Unpack uint32 words [..., W] back to bool [..., n_bits]."""
    words = np.asarray(words, dtype=np.uint32)
    shifts = np.arange(WORD, dtype=np.uint32)
    bits = (words[..., :, None] >> shifts) & np.uint32(1)
    bits = bits.reshape(words.shape[:-1] + (-1,))
    return bits[..., :n_bits].astype(bool)


def np_from_indices(idx: np.ndarray, n_bits: int) -> np.ndarray:
    """Bitset [W] with bits at `idx` set."""
    out = np.zeros(n_words(n_bits), dtype=np.uint32)
    idx = np.asarray(idx, dtype=np.int64)
    np.bitwise_or.at(out, idx >> 5, (np.uint32(1) << (idx & 31).astype(np.uint32)))
    return out


def np_to_indices(words: np.ndarray, n_bits: int) -> np.ndarray:
    """Sorted int64 positions < n_bits of the set bits, along the last axis
    (row after row): `np.nonzero(np_unpack(words, n_bits))[-1]`."""
    return np_indices_nz(words, n_bits)[0]


def np_indices_nz(words: np.ndarray, n_bits: int) -> tuple[np.ndarray, int]:
    """(`np_to_indices(words, n_bits)`, number of nonzero uint32 words).

    Match rows are mostly zero words, so the set bits are found by reading
    the words as uint64 pairs (uint32 when their count is odd) and
    unpacking only the nonzero units. A row with more than half its units
    nonzero is unpacked whole instead: there that costs less than placing
    every bit by its unit's offset.
    """
    words = np.ascontiguousarray(words, dtype="<u4")
    w = words.shape[-1]
    flat = words.reshape(-1)
    units = flat.view("<u8") if flat.size % 2 == 0 else flat
    shift = 6 if units.itemsize == 8 else 5         # log2 of bits a unit
    at = np.flatnonzero(units)
    if 2 * at.size > units.size:
        nz = np.count_nonzero(flat)
        ids = np.flatnonzero(np.unpackbits(
            flat.view(np.uint8), bitorder="little").view(bool))
    else:
        sel = units[at]
        nz = np.count_nonzero(sel.view("<u4"))
        ids = np.flatnonzero(np.unpackbits(
            sel.view(np.uint8), bitorder="little").view(bool))
        ids += ((at - np.arange(at.size)) << shift)[ids >> shift]
    if words.ndim > 1:
        ids %= WORD * w
    if n_bits < WORD * w:
        ids = ids[ids < n_bits]
    return ids, int(nz)


def np_popcount(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words.astype(np.uint32)).sum(axis=-1, dtype=np.int64)


# ---------------------------------------------------------------------------
# jax (device) side
# ---------------------------------------------------------------------------

def pack(bits: jnp.ndarray) -> jnp.ndarray:
    """Pack bool [..., n] -> uint32 [..., W] (n padded up to a word multiple)."""
    n = bits.shape[-1]
    w = n_words(n)
    pad = w * WORD - n
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    bits = bits.reshape(bits.shape[:-1] + (w, WORD))
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32))
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1, dtype=jnp.uint32)


def unpack(words: jnp.ndarray, n_bits: int | None = None) -> jnp.ndarray:
    """Unpack uint32 [..., W] -> bool [..., n_bits or 32*W]."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(words.shape[:-1] + (-1,))
    if n_bits is not None:
        bits = bits[..., :n_bits]
    return bits.astype(bool)


def popcount(words: jnp.ndarray) -> jnp.ndarray:
    """Total set bits along the last axis -> int32 [...]."""
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32), axis=-1)


def count_and_not(a: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """popcount(a & ~mask) along the last axis.

    This is the marginal-gain primitive: `a` is a candidate's incidence row,
    `mask` is the already-covered bitset.
    """
    return popcount(a & ~mask)


def bit_get(words: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather bits at positions `idx` from a flat bitset `words` [W]."""
    word = words[idx >> 5]
    return ((word >> (idx & 31).astype(jnp.uint32)) & jnp.uint32(1)).astype(jnp.bool_)


def or_rows(words: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """OR-reduce a stack of bitsets."""
    return jax.lax.reduce(
        words, jnp.uint32(0), jax.lax.bitwise_or, (axis,)
    )


def from_indices(idx: jnp.ndarray, n_bits: int, valid: jnp.ndarray | None = None,
                 *, unique: bool = False) -> jnp.ndarray:
    """Scatter-OR indices into a fresh bitset [W]. `valid` masks padded entries.

    unique=True (indices guaranteed distinct, e.g. sorted match-set lists):
    scatter-ADD of distinct powers of two is exactly OR — O(U) and scales to
    production bitsets (the one-hot route below is O(U*W) and would build a
    137 GB intermediate for a 2^28-doc universe).

    unique=False: jnp has no scatter-or and scatter-add double-counts
    duplicates, so we go through one-hot over words + OR-reduce; fine for
    U <= a few thousand and small W.
    """
    w = n_words(n_bits)
    bit = jnp.uint32(1) << (idx & 31).astype(jnp.uint32)
    word_idx = idx >> 5
    if valid is not None:
        bit = jnp.where(valid, bit, jnp.uint32(0))
        word_idx = jnp.where(valid, word_idx, 0)
    if unique:
        out = jnp.zeros((w,), jnp.uint32)
        return out.at[word_idx].add(bit, mode="drop")
    onehot = (word_idx[:, None] == jnp.arange(w)[None, :]).astype(jnp.uint32)  # [U, W]
    return or_rows(onehot * bit[:, None], axis=0)


def is_subset(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Elementwise bitset subset test a ⊆ b over the last axis (broadcasts)."""
    return jnp.all((a & b) == a, axis=-1)
