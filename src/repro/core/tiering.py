"""Clause tiering: the ψ/φ classifiers of paper §3.1 + coverage evaluation.

A `ClauseTiering` is the deployable artifact a solve produces: the selected
clause set (packed over vocab for online subset tests), the materialized
Tier-1 document set, and evaluation helpers. `verify_correctness` checks
Theorem 3.1 exhaustively on a query set.
"""
from __future__ import annotations

import dataclasses
import typing

import numpy as np

from repro import obs
from repro.core import bitset

if typing.TYPE_CHECKING:  # avoid circular import (data imports core.bitset)
    from repro.data.incidence import TieringData


@dataclasses.dataclass
class ClauseTiering:
    clauses: list[tuple[int, ...]]
    clause_vocab_bits: np.ndarray     # packed [K, Wv] (ψ: subset test)
    tier1_docs: np.ndarray            # bool [n_docs]  (φ materialized)
    vocab_size: int

    @classmethod
    def from_selection(cls, data: "TieringData", selected: np.ndarray) -> "ClauseTiering":
        idx = np.nonzero(selected)[0]
        clauses = [data.clauses[i] for i in idx]
        cbits = np.zeros((len(clauses), data.corpus.vocab_size), bool)
        for i, c in enumerate(clauses):
            cbits[i, list(c)] = True
        t1 = np.zeros(data.n_docs, bool)
        if len(idx):
            t1_bits = data.clause_doc_bits[idx][0].copy()
            for r in data.clause_doc_bits[idx][1:]:
                t1_bits |= r
            t1 = bitset.np_unpack(t1_bits, data.n_docs)
        return cls(clauses=clauses, clause_vocab_bits=bitset.np_pack(cbits),
                   tier1_docs=t1, vocab_size=data.corpus.vocab_size)

    # ψ^clause (eq. 8): Tier 1 iff some selected clause ⊆ q
    @obs.spanned("classify_log")
    def classify_queries(self, query_bits: np.ndarray, chunk: int = 4096) -> np.ndarray:
        out = np.zeros(query_bits.shape[0], bool)
        if len(self.clauses) == 0:
            return out
        for s in range(0, query_bits.shape[0], chunk):
            q = query_bits[s:s + chunk]                      # [b, Wv]
            sub = (q[:, None, :] & self.clause_vocab_bits[None]) == \
                self.clause_vocab_bits[None]
            out[s:s + chunk] = sub.all(axis=-1).any(axis=1)
        return out

    # φ^clause (eq. 9) for new documents
    def classify_docs(self, doc_bits: np.ndarray) -> np.ndarray:
        return self.classify_queries(doc_bits)

    def coverage(self, data: "TieringData") -> dict[str, float]:
        elig = self.classify_queries(data.log.query_bits)
        return {
            "train": float(data.log.train_weights[elig].sum()),
            "test": float(data.log.test_weights[elig].sum()),
            "tier1_frac": float(self.tier1_docs.mean()),
        }

    def verify_correctness(self, data: "TieringData") -> bool:
        """Theorem 3.1: every eligible query's match set is inside Tier 1."""
        elig = self.classify_queries(data.log.query_bits)
        t1 = bitset.np_pack(self.tier1_docs)
        m_out = data.query_doc_bits & ~t1[None, :]
        ok = ~np.any(m_out, axis=1)
        return bool(np.all(ok[elig]))
