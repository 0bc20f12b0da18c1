"""Plain references the benchmark's `correct` is decided by.

They import nothing of the program and build their own operands from the
benchmark's (doc, term) pairs:

- `Postings`: an inverted index of packed rows; a query's match set is the
  AND of its terms' rows (paper eq. 1).
- `eligible`: psi^clause (paper eq. 8), a subset test of the query against
  a clause set, by enumerating the query's sub-sets.
- `Greedy`: the cost-ratio greedy of paper eq. 13 over the same clause
  universe, resumed from a given prefix, in float64 (or, as the control,
  in bfloat16); the unique-mass prune a re-tiering controller applies
  before a warm refit; the traffic share a clause set makes eligible.
"""
from __future__ import annotations

import itertools

import numpy as np

BIG = 1e12          # ratio of a clause that adds queries and no documents


class Postings:
    """uint32 [V, W] with bit d % 32 of word d // 32 of row t set iff
    document d holds term t."""

    def __init__(self, doc: np.ndarray, term: np.ndarray, n_docs: int,
                 vocab_size: int):
        self.n_docs = n_docs
        self.n_words = (n_docs + 31) // 32
        key = np.sort(term * n_docs + doc)
        t, d = key // n_docs, key % n_docs
        word = t * self.n_words + (d >> 5)
        bit = np.left_shift(np.uint32(1), (d & 31).astype(np.uint32))
        starts = np.flatnonzero(np.r_[True, word[1:] != word[:-1]])
        bits = np.zeros(vocab_size * self.n_words, np.uint32)
        # pairs are distinct, so within a word the sum of bits is their OR
        bits[word[starts]] = np.add.reduceat(bit, starts)
        self.bits = bits.reshape(vocab_size, self.n_words)

    def row_and(self, terms, mask: np.ndarray | None = None) -> np.ndarray:
        """AND of the terms' rows (and of a packed `mask`, if given)."""
        terms = list(terms)
        row = self.bits[terms[0]].copy()
        for t in terms[1:]:
            row &= self.bits[t]
        if mask is not None:
            row &= mask
        return row

    def pack(self, docs: np.ndarray) -> np.ndarray:
        """Packed row of a bool [n_docs] document set."""
        bits = np.zeros(self.n_words * 32, np.uint8)
        bits[:self.n_docs] = docs
        return np.packbits(bits, bitorder="little").view(np.uint32)

    def unpack(self, row: np.ndarray) -> np.ndarray:
        return np.unpackbits(row.view(np.uint8),
                             bitorder="little")[:self.n_docs]

    def ids(self, terms, mask: np.ndarray | None = None) -> np.ndarray:
        return np.flatnonzero(self.unpack(self.row_and(terms, mask)))

    def count(self, terms, mask: np.ndarray | None = None) -> int:
        return int(np.bitwise_count(self.row_and(terms, mask)).sum())


def subsets(query: tuple[int, ...]):
    for r in range(1, len(query) + 1):
        yield from itertools.combinations(query, r)


def eligible(query: tuple[int, ...], clauses: set) -> bool:
    """Tier 1 iff some clause is a subset of the query."""
    return any(s in clauses for s in subsets(tuple(sorted(query))))


def to_bf16(x) -> np.ndarray:
    import ml_dtypes
    return np.asarray(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16),
                      np.float64)


class Greedy:
    """Cost-ratio greedy over clauses: f is the weight of the queries some
    selected clause is a subset of, g the number of documents some selected
    clause matches; each step adds the feasible clause of highest f gain
    over g gain (lowest index on ties) while g stays within the budget."""

    CHUNK = 1 << 16

    def __init__(self, postings: Postings, clauses: list[tuple[int, ...]],
                 queries: list[tuple[int, ...]]):
        self.clauses = [tuple(sorted(c)) for c in clauses]
        index = {c: i for i, c in enumerate(self.clauses)}
        self.packed = np.stack([postings.row_and(c) for c in self.clauses])
        self.dense = np.unpackbits(self.packed.view(np.uint8), axis=1,
                                   bitorder="little")[:, :postings.n_docs]
        self.a_q = np.zeros((len(self.clauses), len(queries)), bool)
        for qi, q in enumerate(queries):
            for s in subsets(tuple(sorted(q))):
                ci = index.get(s)
                if ci is not None:
                    self.a_q[ci, qi] = True
        self.a_qf = self.a_q.astype(np.float64)

    def prune(self, selected: np.ndarray, weights: np.ndarray,
              min_mass: float, rel_tol: float = 1e-4
              ) -> tuple[np.ndarray, np.ndarray]:
        """Selected clauses whose unique mass (the weight of the queries no
        other selected clause covers) is at least `min_mass`, and those
        within `rel_tol` of it, where float32 sums may fall either way."""
        idx = np.flatnonzero(selected)
        cover = self.a_q[idx]
        once = cover.sum(axis=0) == 1
        mass = self.a_qf[idx][:, once] @ np.asarray(weights, np.float64)[once]
        near = np.abs(mass - min_mass) <= rel_tol * min_mass
        return idx[mass >= min_mass], idx[near]

    def coverage(self, selected: np.ndarray, weights: np.ndarray) -> float:
        """Traffic share a selection sends to Tier 1 (psi^clause)."""
        eligible = self.a_q[np.flatnonzero(selected)].any(axis=0)
        return float(np.asarray(weights, np.float64)[eligible].sum())

    def union(self, selected: np.ndarray) -> np.ndarray:
        """Packed union of the selected clauses' match sets."""
        idx = np.flatnonzero(selected)
        if not len(idx):
            return np.zeros(self.packed.shape[1], np.uint32)
        return np.bitwise_or.reduce(self.packed[idx], axis=0)

    def solve(self, weights: np.ndarray, budget: float, prefix,
              bf16: bool = False) -> list[int]:
        """Clause ids the greedy adds after the `prefix`, in order."""
        return self._run(weights, budget, prefix, None, bf16)[0]

    def replay(self, weights: np.ndarray, budget: float, prefix,
               order) -> tuple[float, bool]:
        """Follow a solver's own choices from `prefix`; returns the widest
        relative gap by which a chosen clause's f/g ratio lay below the
        best feasible ratio (1 for an infeasible or repeated choice), and
        whether a feasible clause was left when the choices ran out."""
        _, gap, left = self._run(weights, budget, prefix, list(order), False)
        return gap, left

    def _run(self, weights, budget, prefix, order, bf16):
        n = len(self.clauses)
        selected = np.zeros(n, bool)
        selected[np.asarray(prefix, np.int64)] = True
        w = to_bf16(weights) if bf16 else np.asarray(weights, np.float64)
        covered_q = self.a_q[selected].any(axis=0)
        cov = self.union(selected)
        used = int(np.bitwise_count(cov).sum())
        gg = np.bitwise_count(self.packed & ~cov).sum(axis=1, dtype=np.int64)
        uncovered = np.unpackbits((~cov).view(np.uint8), bitorder="little"
                                  )[:self.dense.shape[1]]
        chosen, gap = [], 0.0
        while True:
            fg = self.a_qf @ (w * ~covered_q)
            if bf16:
                fg = to_bf16(fg)
            feasible = ~selected & (fg > 0) & (used + gg <= budget)
            if order is not None and len(chosen) == len(order):
                return chosen, gap, bool(feasible.any())
            if not feasible.any():
                if order is not None:       # the solver went on: infeasible
                    gap = 1.0
                return chosen, gap, False
            with np.errstate(divide="ignore", invalid="ignore"):
                ratio = np.where(gg <= 0, fg * BIG,
                                 fg / np.maximum(gg, 1e-30))
            if bf16:
                ratio = to_bf16(ratio)
            score = np.where(feasible, ratio, -np.inf)
            if order is None:
                j = int(np.argmax(score))
            else:
                j = int(order[len(chosen)])
                best = float(score.max())
                gap = max(gap, 1.0 if not feasible[j]
                          else 1.0 - float(ratio[j]) / best)
            chosen.append(j)
            selected[j] = True
            covered_q |= self.a_q[j]
            newly = np.flatnonzero(self.dense[j] & uncovered)
            uncovered[newly] = 0
            used += len(newly)
            for lo in range(0, len(newly), self.CHUNK):
                gg -= self.dense[:, newly[lo:lo + self.CHUNK]].sum(
                    axis=1, dtype=np.int64)
