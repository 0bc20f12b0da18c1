"""Seeded corpus and query log of one deployment, made by the benchmark.

The law is the one a configuration file states. A passage is
Poisson(`passage_words_mean`) word tokens and a query Poisson(
`query_words_mean`); only the share `head_token_share` of tokens falls
among the `vocab_size` head terms the index holds, so a passage holds
Poisson(mean x share) head tokens, drawn iid by Zipf(`term_zipf_a`) over
the head ranks, each distinct term once. A query is that many distinct
head terms of one passage (at least 1, at most `max_query_len`), so
every query matches; the pool has Zipf popularity and multinomial
train/test logs. It is kept here, apart from the program's own data
generator, so that no change to the program moves the workload.

Every seed does the same work: the layout (each passage's and query's
length, each token's Zipf rank, the pool and its popularity) is drawn
from one fixed seed, and `seed` names what the layout holds: which term
id carries each Zipf rank and which document id each passage. So every
seed mines as many clauses, compiles the same shapes and serves answers
of the same sizes, over other ids.

`make(cfg, seed)` returns a `Corpus` of plain numpy arrays; `to_program`
wraps it in the program's input types.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Corpus:
    n_docs: int
    vocab_size: int
    doc: np.ndarray           # int64 [P] (doc, term) pairs, sorted by doc
    term: np.ndarray          # int64 [P]   and by term within a doc
    queries: list[tuple[int, ...]]   # unique queries, sorted term tuples
    train_weights: np.ndarray        # f64 [Nq]
    test_weights: np.ndarray         # f64 [Nq]
    n_train: int
    n_test: int


def zipf_probs(n: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** a
    return p / p.sum()


LAYOUT = 20050790      # seed of the one corpus layout all runs share


def draw_tokens(rng: np.random.Generator, probs: np.ndarray,
                tokens: np.ndarray, doc_of_slot: np.ndarray,
                term_of_rank: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(doc, term) pairs, sorted: passage s draws `tokens[s]` iid ranks
    from `probs` and holds each distinct one once, as document
    `doc_of_slot[s]` and terms `term_of_rank[rank]`."""
    vocab = len(probs)
    cdf = np.cumsum(probs)
    cdf /= cdf[-1]
    slot = np.repeat(np.arange(len(tokens), dtype=np.int64), tokens)
    rank = np.searchsorted(cdf, rng.random(slot.size), side="right")
    keys = np.unique(doc_of_slot[slot] * vocab
                     + term_of_rank[np.minimum(rank, vocab - 1)])
    return keys // vocab, keys % vocab


def thinned(rng: np.random.Generator, words_mean: float, share: float,
            size: int, most: int | None = None) -> np.ndarray:
    """Head tokens of `size` texts of Poisson(`words_mean`) words, at
    least 1 (and at most `most`)."""
    k = np.maximum(1, rng.poisson(words_mean * share, size=size))
    return k if most is None else np.minimum(k, most)


def make(cfg: dict, seed: int) -> Corpus:
    rng = np.random.default_rng(LAYOUT)
    n_docs, vocab = int(cfg["n_docs"]), int(cfg["vocab_size"])
    names = np.random.default_rng([seed, 0])
    term_of_rank = names.permutation(vocab)
    doc_of_slot = names.permutation(n_docs)
    rank_of_term = np.argsort(term_of_rank)
    share = float(cfg["head_token_share"])
    doc, term = draw_tokens(
        rng, zipf_probs(vocab, cfg["term_zipf_a"]),
        thinned(rng, cfg["passage_words_mean"], share, n_docs),
        doc_of_slot, term_of_rank)

    # query pool: sub-sets of document term sets (every query matches),
    # each picked from its passage's terms in rank order, as laid out
    ptr = np.searchsorted(doc, np.arange(n_docs + 1))
    pool: dict[tuple[int, ...], None] = {}
    size = int(cfg["query_pool"])
    while len(pool) < size:
        need = size - len(pool)
        for s, k in zip(rng.integers(0, n_docs, size=2 * need),
                        thinned(rng, cfg["query_words_mean"], share,
                                2 * need, int(cfg["max_query_len"]))):
            d = doc_of_slot[s]
            terms = term[ptr[d]:ptr[d + 1]]
            terms = terms[np.argsort(rank_of_term[terms])]
            pick = rng.choice(terms, size=int(min(k, len(terms))),
                              replace=False)
            pool[tuple(sorted(int(t) for t in pick))] = None
            if len(pool) >= size:
                break
    queries = list(pool)
    popularity = zipf_probs(len(queries), cfg["query_zipf_a"])
    popularity = popularity[rng.permutation(len(queries))]
    n_train, n_test = int(cfg["n_train"]), int(cfg["n_test"])
    train = rng.multinomial(n_train, popularity)
    test = rng.multinomial(n_test, popularity)
    keep = (train + test) > 0
    return Corpus(
        n_docs=n_docs, vocab_size=vocab, doc=doc, term=term,
        queries=[q for q, k in zip(queries, keep) if k],
        train_weights=train[keep] / n_train, test_weights=test[keep] / n_test,
        n_train=n_train, n_test=n_test)


def to_program(c: Corpus):
    """The program's `(Corpus, QueryLog)` input types for the same data."""
    from repro.core import bitset
    from repro.data.synthetic import Corpus as PCorpus, QueryLog

    ptr = np.searchsorted(c.doc, np.arange(c.n_docs + 1)).tolist()
    flat = c.term.tolist()
    doc_tokens = [tuple(flat[a:b]) for a, b in zip(ptr[:-1], ptr[1:])]
    corpus = PCorpus(doc_tokens=doc_tokens,
                     doc_bits=bitset.np_pack_pairs(c.doc, c.term, c.n_docs,
                                                   c.vocab_size),
                     vocab_size=c.vocab_size)
    log = QueryLog(queries=list(c.queries),
                   query_bits=bitset.np_pack_sets(c.queries, c.vocab_size),
                   train_weights=c.train_weights, test_weights=c.test_weights,
                   n_train_samples=c.n_train, n_test_samples=c.n_test)
    return corpus, log
