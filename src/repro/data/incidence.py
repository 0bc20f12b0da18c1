"""Incidence-structure builders: postings, match sets, clause incidence.

Turns the host-side corpus/query log into the packed-bitset operands the SCSK
engine consumes:

  postings_bits     uint32 [V, Wd]   token -> doc bitset (the inverted index)
  clause_doc_bits   uint32 [C, Wd]   m(c) per clause  (paper eq. 1, AND of postings)
  clause_query_bits uint32 [C, Wq]   {q : c ⊆ q} per clause
  query_doc_bits    uint32 [Nq, Wd]  m(q) per unique query (flow baselines)
  clause_doc_ids    int32  [C, M]    padded+sorted m(c) id lists (sparse path)

`append_docs` grows every one of those structures in place by a whole-word
document block (repro.ingest): existing words are NEVER rewritten, so any
column slice taken before the append stays bit-identical afterwards — the
invariant the cluster's content-carried rolling postings swaps rely on.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import bitset
from repro.data.synthetic import Corpus, QueryLog


def build_postings(corpus: Corpus) -> np.ndarray:
    """Packed postings lists: bit d of row v set iff v ∈ doc d. Packed
    straight from the (term, doc) pairs, so no [V, n_docs] bool plane."""
    return bitset.np_pack_sets(corpus.doc_tokens, corpus.vocab_size,
                               transpose=True)


def match_bits(postings: np.ndarray, clause: tuple[int, ...], n_docs: int) -> np.ndarray:
    """m(clause) as a packed bitset: AND of the clause terms' postings."""
    return _and_rows(postings, [clause], n_docs)[0]


def _and_rows(postings: np.ndarray, sets: list[tuple[int, ...]],
              n_bits: int) -> np.ndarray:
    """[len(sets), W]: AND of each set's postings rows, padding bits beyond
    `n_bits` cleared (an empty set matches every valid bit)."""
    pad_mask = bitset.np_pack(np.ones(n_bits, dtype=bool))
    out = np.empty((len(sets), postings.shape[1]), np.uint32)
    for i, s in enumerate(sets):
        row = pad_mask.copy()
        for t in s:
            row &= postings[t]
        out[i] = row
    return out


def clause_doc_incidence(postings: np.ndarray, clauses: list[tuple[int, ...]],
                         n_docs: int) -> np.ndarray:
    return _and_rows(postings, clauses, n_docs)


def clause_query_incidence(
    query_bits: np.ndarray,            # packed [Nq, Wv]
    clauses: list[tuple[int, ...]],
    vocab_size: int,
) -> np.ndarray:
    """Packed [C, Wq]: bit q of row c set iff c ⊆ q — the AND of the
    clause terms' rows of the query-side inverted index."""
    nq = query_bits.shape[0]
    q_of_term = bitset.np_pack(
        bitset.np_unpack(query_bits, vocab_size).T)   # [V, Wq]
    return _and_rows(q_of_term, clauses, nq)


def query_doc_incidence(postings: np.ndarray, log: QueryLog, n_docs: int) -> np.ndarray:
    """m(q) per unique query, packed [Nq, Wd] (used by flow baselines)."""
    return _and_rows(postings, log.queries, n_docs)


def padded_id_lists(rows_bits: np.ndarray, n_bits: int,
                    pad_to: int | None = None) -> np.ndarray:
    """Packed rows -> int32 [R, M] sorted id lists padded with -1."""
    lists = [bitset.np_to_indices(r, n_bits) for r in rows_bits]
    m = pad_to or max((len(x) for x in lists), default=1)
    out = np.full((len(lists), max(m, 1)), -1, dtype=np.int32)
    for i, x in enumerate(lists):
        out[i, :len(x)] = x          # np.nonzero is already sorted
    return out


@dataclasses.dataclass
class TieringData:
    """Everything the solvers and baselines need, in host numpy."""
    corpus: Corpus
    log: QueryLog
    postings: np.ndarray             # [V, Wd]
    clauses: list[tuple[int, ...]]
    clause_support: np.ndarray       # f64 [C] empirical P[c ⊆ q]
    clause_doc_bits: np.ndarray      # [C, Wd]
    clause_query_bits: np.ndarray    # [C, Wq]
    query_doc_bits: np.ndarray       # [Nq, Wd]

    @property
    def n_docs(self) -> int:
        return self.corpus.n_docs

    @property
    def n_queries(self) -> int:
        return self.log.n_queries


@dataclasses.dataclass(frozen=True)
class AppendDelta:
    """What one `append_docs` call added, in block coordinates.

    The block is word-aligned: it starts at word `word_lo` (doc id
    `doc_lo = word_lo * 32`), which means up to 31 hole slots pad the
    previous tail word first. Holes are permanent empty documents — `()`
    token sets with zero bits in every incidence structure — so no existing
    postings word is ever rewritten and they can never match any clause or
    query. `clause_cols` is the appended clause×block incidence, ready for
    `SCSKProblem.with_doc_block`.
    """
    doc_lo: int                # global id of the first appended slot (hole or doc)
    n_holes: int               # alignment padding slots before the real docs
    n_new: int                 # real documents appended
    word_lo: int               # first appended postings word (inclusive)
    word_hi: int               # one past the last appended word == new Wd
    clause_cols: np.ndarray    # uint32 [C, word_hi - word_lo] block m(c) columns
    n_docs: int                # corpus.n_docs after the append (incl. holes)


def append_docs(data: "TieringData", docs: list[tuple[int, ...]]) -> AppendDelta:
    """Append a word-aligned document block to every incidence structure.

    Mutates `data` (corpus, postings, clause_doc_bits, query_doc_bits) in
    place and returns the `AppendDelta` describing the block. Append-only in
    whole words: the block starts at the next word boundary (hole slots fill
    the tail partial word), new columns are computed only over the block —
    O((V + C + Nq) · block_words) — and concatenated, so every pre-existing
    word keeps its exact bits. Clause/query *vocab*-side structures are
    untouched: documents don't change the query universe.
    """
    if not docs:
        raise ValueError("append_docs needs at least one document")
    corpus = data.corpus
    word_lo = data.postings.shape[1]
    doc_lo = word_lo * bitset.WORD
    n_holes = doc_lo - corpus.n_docs
    n_new = len(docs)
    n_docs_new = doc_lo + n_new
    word_hi = bitset.n_words(n_docs_new)

    for t in docs:
        bad = [v for v in t if not 0 <= int(v) < corpus.vocab_size]
        if bad:
            raise ValueError(f"document tokens {bad} outside vocab "
                             f"[0, {corpus.vocab_size})")
    corpus.doc_tokens.extend([()] * n_holes)
    corpus.doc_tokens.extend(tuple(sorted(set(int(v) for v in t)))
                             for t in docs)

    # block postings [V, wb]: bit (d - doc_lo) of row v set iff v ∈ doc d
    wb = word_hi - word_lo
    new_docs = corpus.doc_tokens[doc_lo:]
    # doc_lo is word-aligned, so block-local ids pack into whole words
    blk_postings = bitset.np_pack_sets(new_docs, corpus.vocab_size,
                                       transpose=True)
    # corpus doc_bits rows: holes are all-zero rows, then the packed docs
    hole_rows = np.zeros((n_holes, corpus.doc_bits.shape[1]), np.uint32)
    doc_rows = bitset.np_pack_sets(new_docs, corpus.vocab_size)
    corpus.doc_bits = np.concatenate([corpus.doc_bits, hole_rows, doc_rows])

    # incidence columns over the block only (block doc ids are local)
    clause_cols = clause_doc_incidence(blk_postings, data.clauses, n_new)
    query_cols = query_doc_incidence(blk_postings, data.log, n_new) \
        if data.log.queries else np.zeros((0, wb), np.uint32)

    data.postings = np.concatenate([data.postings, blk_postings], axis=1)
    data.clause_doc_bits = np.concatenate(
        [data.clause_doc_bits, clause_cols], axis=1)
    data.query_doc_bits = np.concatenate(
        [data.query_doc_bits, query_cols], axis=1)
    return AppendDelta(doc_lo=doc_lo - n_holes, n_holes=n_holes, n_new=n_new,
                       word_lo=word_lo, word_hi=word_hi,
                       clause_cols=clause_cols, n_docs=corpus.n_docs)


def build_tiering_data(corpus: Corpus, log: QueryLog, *, min_support: float,
                       max_clause_len: int = 4,
                       max_clauses: int | None = None) -> TieringData:
    from repro.data import mining
    # mine with head-room, THEN keep the top-support clauses: fpgrowth's
    # max_items stops recursion mid-mining (an arbitrary subset, not the
    # most frequent patterns)
    mined = mining.fpgrowth(
        log.queries, list(log.train_weights), min_support,
        max_len=max_clause_len,
        max_items=None if max_clauses is None else 10 * max_clauses)
    clauses = sorted(mined, key=lambda c: (-mined[c], c))
    if max_clauses is not None:
        clauses = clauses[:max_clauses]
    postings = build_postings(corpus)
    return TieringData(
        corpus=corpus,
        log=log,
        postings=postings,
        clauses=clauses,
        clause_support=np.array([mined[c] for c in clauses]),
        clause_doc_bits=clause_doc_incidence(postings, clauses, corpus.n_docs),
        clause_query_bits=clause_query_incidence(
            log.query_bits, clauses, corpus.vocab_size),
        query_doc_bits=query_doc_incidence(postings, log, corpus.n_docs),
    )
