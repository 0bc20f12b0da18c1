"""Synthetic corpus + heavy-tailed query-distribution generator.

Mirrors the statistics the paper reports for its commercial-search data at a
CPU-tractable scale: a Zipfian vocabulary, documents as term sets, and a query
distribution with (a) a Zipfian head, and (b) a heavy tail such that a
substantial fraction of *test* queries never appear in the *training* log —
exactly the regime where the paper's clause method beats query-selection
(flow) methods, cf. paper §2.3 and Fig. 5.

Everything here is host-side numpy preprocessing (the paper's analogue is
Lucene indexing); device arrays are produced by data/incidence.py.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import bitset


@dataclasses.dataclass
class Corpus:
    doc_tokens: list[tuple[int, ...]]   # sorted term ids per doc
    doc_bits: np.ndarray                # packed uint32 [n_docs, Wv] over vocab
    vocab_size: int

    @property
    def n_docs(self) -> int:
        return len(self.doc_tokens)


@dataclasses.dataclass
class QueryLog:
    """Unique queries with empirical train/test probabilities.

    train_weights/test_weights are empirical probabilities over the union of
    unique queries; a query unseen in train has train_weights == 0 (the
    "novel traffic" the paper's method must generalize to).
    """
    queries: list[tuple[int, ...]]
    query_bits: np.ndarray              # packed uint32 [Nq, Wv] over vocab
    train_weights: np.ndarray           # f64 [Nq], sums to 1
    test_weights: np.ndarray            # f64 [Nq], sums to 1
    n_train_samples: int
    n_test_samples: int

    @property
    def n_queries(self) -> int:
        return len(self.queries)

    def novel_test_mass(self) -> float:
        """Fraction of test traffic on queries unseen in training."""
        return float(self.test_weights[self.train_weights == 0].sum())


def _zipf_probs(n: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** a
    return p / p.sum()


def _choice_terms(rng: np.random.Generator, probs: np.ndarray,
                  lengths: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(doc, term) pairs, one `rng.choice(replace=False, p=probs)` per doc.

    The RNG stream the small presets were generated with; each draw is O(V).
    """
    vocab_size = len(probs)
    terms = [np.unique(rng.choice(vocab_size, size=int(min(k, vocab_size)),
                                  replace=False, p=probs)) for k in lengths]
    doc = np.repeat(np.arange(len(terms)), [len(t) for t in terms])
    return doc, _concat(terms)


def _bulk_terms(rng: np.random.Generator, probs: np.ndarray,
                lengths: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(doc, term) pairs with the law of `_choice_terms`, drawn in bulk.

    `rng.choice(replace=False, p=...)` draws with replacement, keeps the
    distinct terms and redraws the shortfall: each doc gets the first k
    distinct terms of an iid stream from `probs`. Here every doc that is
    still short draws its shortfall in one vectorised round, so the cost is
    O(pairs · log V) rather than O(V) per doc.
    """
    vocab_size = len(probs)
    cdf = np.cumsum(probs)
    cdf /= cdf[-1]
    need = np.minimum(lengths, vocab_size).astype(np.int64)
    found: list[np.ndarray] = []
    pending = np.zeros(0, np.int64)     # accepted keys of docs still short
    active = np.arange(len(need))
    while active.size:
        doc = np.repeat(active, need[active])
        term = np.searchsorted(cdf, rng.random(doc.size), side="right")
        keys = doc * vocab_size + term
        fresh = np.unique(keys[~np.isin(keys, pending)])
        found.append(fresh)
        need -= np.bincount(fresh // vocab_size, minlength=len(need))
        pending = np.concatenate([pending, fresh])
        pending = pending[need[pending // vocab_size] > 0]
        active = active[need[active] > 0]
    keys = np.sort(_concat(found))
    return keys // vocab_size, keys % vocab_size


def _concat(parts: list[np.ndarray]) -> np.ndarray:
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


def _pairs_to_tokens(doc: np.ndarray, term: np.ndarray,
                     n_docs: int) -> list[tuple[int, ...]]:
    """(doc, term) pairs sorted by doc then term -> sorted term tuples."""
    ptr = np.searchsorted(doc, np.arange(n_docs + 1))
    flat = term.tolist()
    return [tuple(flat[a:b]) for a, b in zip(ptr[:-1], ptr[1:])]


def make_corpus(
    rng: np.random.Generator,
    *,
    vocab_size: int = 2000,
    n_docs: int = 20000,
    doc_len_mean: float = 8.0,
    zipf_a: float = 1.05,
    bulk: bool = False,
) -> Corpus:
    """Zipfian term-set documents. `bulk` draws every doc's terms in
    vectorised rounds (same law, different RNG stream) — what an index of
    millions of documents needs; the default keeps the per-doc stream the
    small presets were generated with."""
    probs = _zipf_probs(vocab_size, zipf_a)
    # shuffle so token id is not rank (more realistic hashing)
    perm = rng.permutation(vocab_size)
    probs = probs[perm]
    lengths = np.maximum(2, rng.poisson(doc_len_mean, size=n_docs))
    doc, term = (_bulk_terms if bulk else _choice_terms)(rng, probs, lengths)
    return Corpus(doc_tokens=_pairs_to_tokens(doc, term, n_docs),
                  doc_bits=bitset.np_pack_pairs(doc, term, n_docs, vocab_size),
                  vocab_size=vocab_size)


def make_query_log(
    rng: np.random.Generator,
    corpus: Corpus,
    *,
    pool_size: int = 30000,
    n_train: int = 200000,
    n_test: int = 70000,
    max_query_len: int = 4,
    zipf_a: float = 0.9,
) -> QueryLog:
    """Build a query pool by sub-sampling document term sets (non-empty match
    sets guaranteed), Zipf-weight the pool, and draw iid train/test logs."""
    n_docs = corpus.n_docs
    pool: dict[tuple[int, ...], None] = {}
    while len(pool) < pool_size:
        need = pool_size - len(pool)
        doc_idx = rng.integers(0, n_docs, size=need * 2)
        sizes = rng.integers(1, max_query_len + 1, size=need * 2)
        for di, sz in zip(doc_idx, sizes):
            d = corpus.doc_tokens[int(di)]
            if len(d) == 0:
                continue
            sz = int(min(sz, len(d)))
            q = tuple(sorted(int(t) for t in rng.choice(d, size=sz, replace=False)))
            pool[q] = None
            if len(pool) >= pool_size:
                break
    queries = list(pool.keys())
    pool_probs = _zipf_probs(len(queries), zipf_a)
    pool_probs = pool_probs[rng.permutation(len(queries))]

    train_counts = rng.multinomial(n_train, pool_probs)
    test_counts = rng.multinomial(n_test, pool_probs)
    keep = (train_counts + test_counts) > 0
    queries = [q for q, k in zip(queries, keep) if k]
    train_counts = train_counts[keep]
    test_counts = test_counts[keep]

    return QueryLog(
        queries=queries,
        query_bits=bitset.np_pack_sets(queries, corpus.vocab_size),
        train_weights=train_counts / max(1, n_train),
        test_weights=test_counts / max(1, n_test),
        n_train_samples=n_train,
        n_test_samples=n_test,
    )


_MEDIUM = dict(vocab_size=2000, n_docs=20000, doc_len_mean=8.0,
               pool=30000, n_train=200000, n_test=70000)
# One chip's quarter of the MS MARCO passage-ranking collection (8,841,823
# passages): 2^21 docs over 8,192 dense-bitset head terms (an assumed head:
# tail terms need hybrid postings), 8,192 unique queries, and medium's
# document length and train/test draws.
_PASSAGE = dict(_MEDIUM, vocab_size=8192, n_docs=2**21, pool=8192, bulk=True)

PRESETS = {
    "tiny": dict(vocab_size=64, n_docs=200, doc_len_mean=6.0,
                 pool=400, n_train=4000, n_test=1500),
    "small": dict(vocab_size=800, n_docs=4000, doc_len_mean=8.0,
                  pool=6000, n_train=60000, n_test=20000),
    "medium": _MEDIUM,
    "passage": _PASSAGE,
}


def make_tiering_dataset(seed: int = 0, scale: str = "small", *,
                         n_docs: int | None = None):
    """One-call dataset factory. Scales: tiny (tests), small (benches),
    medium (solver benchmarks), passage (one chip's index). `n_docs`
    overrides the preset's document count, e.g. four chips' passage
    shards in one index."""
    rng = np.random.default_rng(seed)
    p = PRESETS[scale]
    corpus = make_corpus(rng, vocab_size=p["vocab_size"],
                         n_docs=p["n_docs"] if n_docs is None else n_docs,
                         doc_len_mean=p["doc_len_mean"],
                         bulk=p.get("bulk", False))
    log = make_query_log(rng, corpus, pool_size=p["pool"],
                         n_train=p["n_train"], n_test=p["n_test"])
    return corpus, log
