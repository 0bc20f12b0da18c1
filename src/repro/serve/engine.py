"""Two-tier serving engine (paper Fig. 1) with clause query classification.

Request path per batch:
  1. ψ^clause — packed subset test of the selected clauses against each query
  2. eligible queries  -> Tier-1 match (postings restricted to D₁)
  3. ineligible queries -> Tier-2 match (full postings)
Theorem 3.1 guarantees step 2 returns the COMPLETE match set for eligible
queries; `TieredEngine.serve` asserts nothing silently — the integration test
compares every result against single-tier matching.

Cost accounting: Tier-1 postings only index |D₁| docs, so a Tier-1 match
touches ~|D₁|/|D| of the word traffic — the engine reports both tiers' word
traffic so benchmarks can translate coverage into served-cost savings (the
paper's "half-sized Tier 1 needs half the machines" argument, §2.2).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bitset
from repro.core.tiering import ClauseTiering
from repro.serve import matching

# registry instruments the engine publishes into (self-gating: these are
# no-ops under REPRO_OBS=0, and ServeStats stays the source of truth either
# way — the counters are a fleet-aggregated VIEW, never an input)
_QUERIES = obs.counter("serve_queries_total", "queries served")
_T1_HITS = obs.counter("serve_tier1_hits_total",
                       "queries answered entirely from Tier 1")
_WORDS = obs.counter("serve_words_total",
                     "postings words scanned", labels=("tier",))


@dataclasses.dataclass
class ServeStats:
    n_queries: int = 0
    n_tier1: int = 0
    tier1_words: int = 0            # postings words scanned in tier 1
    tier2_words: int = 0
    full_words_per_query: int = 0   # untiered per-query traffic (denominator)
    cache_hits: int = 0             # front-end result-cache hits (zero words
    #                                 scanned; cluster.frontend.ResultCache)

    @property
    def tier1_fraction(self) -> float:
        return self.n_tier1 / max(1, self.n_queries)

    @property
    def cache_hit_rate(self) -> float:
        return self.cache_hits / max(1, self.n_queries)

    @property
    def cost_saving(self) -> float:
        """Word-traffic saving vs an untiered (Tier-2-only) system."""
        base = self.n_queries * self.full_words_per_query
        if base == 0:
            return 0.0
        return 1.0 - (self.tier1_words + self.tier2_words) / base

    def reset(self) -> None:
        """Zero the traffic counters (window boundary); the engine-constant
        `full_words_per_query` survives so ratios keep meaning."""
        self.n_queries = self.n_tier1 = 0
        self.tier1_words = self.tier2_words = 0
        self.cache_hits = 0

    def merge(self, other: "ServeStats") -> "ServeStats":
        """Fold another window's counters into this one, in place."""
        if self.full_words_per_query == 0:
            self.full_words_per_query = other.full_words_per_query
        elif other.full_words_per_query not in (0, self.full_words_per_query):
            raise ValueError(
                "merging stats from engines with different postings widths "
                f"({self.full_words_per_query} vs {other.full_words_per_query})")
        self.n_queries += other.n_queries
        self.n_tier1 += other.n_tier1
        self.tier1_words += other.tier1_words
        self.tier2_words += other.tier2_words
        self.cache_hits += other.cache_hits
        return self

    def snapshot(self) -> "ServeStats":
        """Detached copy (per-window reporting while counters keep running)."""
        return dataclasses.replace(self)

    def to_dict(self) -> dict:
        """JSON-ready dict: raw counters + the derived ratios (the uniform
        exporter payload; `from_dict` ignores the derived keys)."""
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d["tier1_fraction"] = self.tier1_fraction
        d["cost_saving"] = self.cost_saving
        d["cache_hit_rate"] = self.cache_hit_rate
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ServeStats":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


@dataclasses.dataclass(frozen=True)
class TieringBuffer:
    """An off-path-built Tier-1 generation, ready to swap in."""
    tiering: ClauseTiering
    postings_t1: jnp.ndarray
    tier1_words_per_query: int
    generation: int = 0


class TieredEngine:
    def __init__(self, postings: np.ndarray, tiering: ClauseTiering,
                 n_docs: int):
        self.n_docs = n_docs
        self.corpus_version = 0
        self._postings_host = np.asarray(postings)   # for re-tiering builds
        self.postings_t2 = jnp.asarray(postings)
        self._live = self.prepare_tiering(tiering)   # generation 0
        self.stats = ServeStats(
            full_words_per_query=postings.shape[1])

    # the live generation is ONE reference: readers grab self._live once per
    # batch, so (ψ, Tier-1 index) always come from the same clause selection
    @property
    def tiering(self) -> ClauseTiering:
        return self._live.tiering

    @property
    def postings_t1(self) -> jnp.ndarray:
        return self._live.postings_t1

    @property
    def tier1_words_per_query(self) -> int:
        return self._live.tier1_words_per_query

    @property
    def generation(self) -> int:
        return self._live.generation

    # -- zero-downtime re-tiering ---------------------------------------------
    @obs.spanned("prepare")
    def prepare_tiering(self, tiering: ClauseTiering) -> TieringBuffer:
        """Build the next Tier-1 generation OFF the request path.

        All the expensive work — masking the postings matrix to the new D₁
        and shipping it to device — happens here, against local buffers; the
        live generation keeps serving untouched.
        """
        with obs.span("build"):
            host_t1 = matching.tier_postings(self._postings_host,
                                             tiering.tier1_docs)
        with obs.span("upload") as sp:
            postings_t1 = sp.sync(jnp.asarray(host_t1))
        # a production Tier-1 re-indexes with a compacted |D1| doc space:
        # its per-query word traffic is ceil(|D1|/32), not the full W.
        words = bitset.n_words(int(tiering.tier1_docs.sum()))
        return TieringBuffer(tiering=tiering, postings_t1=postings_t1,
                             tier1_words_per_query=words)

    @obs.spanned("rollout")
    def swap_tiering(self, tiering: ClauseTiering | TieringBuffer) -> int:
        """Atomically route traffic to a new tiering; returns the generation.

        Accepts either a raw `ClauseTiering` (built off-path here) or a
        `TieringBuffer` from `prepare_tiering`. The commit is a SINGLE
        reference store of the whole generation, and `serve` reads that
        reference exactly once per batch — a batch sees either the old
        (ψ, Tier-1 index) pair or the new one, never a mix, so Theorem 3.1
        completeness holds on both sides of the swap.
        """
        buf = tiering if isinstance(tiering, TieringBuffer) \
            else self.prepare_tiering(tiering)
        self._live = dataclasses.replace(
            buf, generation=self._live.generation + 1)
        obs.event("tiering_swap", generation=self._live.generation,
                  corpus_version=self.corpus_version)
        return self._live.generation

    def swap_corpus(self, postings: np.ndarray, n_docs: int,
                    tiering: ClauseTiering, *,
                    immediate: bool = True) -> int:
        """Swap to an appended corpus snapshot (repro.ingest).

        A single engine has one copy of each tier, so the swap is
        stop-the-world by nature: both tiers and ψ move in one reference
        store between batches (`immediate` is accepted for cluster-facade
        parity but a single engine cannot roll). Append-only growth means
        every already-served match set stays valid at the new version.
        """
        del immediate                    # single engine: always atomic
        postings = np.asarray(postings)
        if n_docs < self.n_docs or \
                postings.shape[1] < self._postings_host.shape[1]:
            raise ValueError(
                f"corpus swaps are append-only: got {n_docs} docs x "
                f"{postings.shape[1]} words, have {self.n_docs} x "
                f"{self._postings_host.shape[1]}")
        self._postings_host = postings
        self.postings_t2 = jnp.asarray(postings)
        self.n_docs = n_docs
        self.corpus_version += 1
        self.stats.full_words_per_query = int(postings.shape[1])
        obs.event("corpus_swap", corpus_version=self.corpus_version,
                  n_docs=self.n_docs, mode="immediate")
        return self.swap_tiering(tiering)

    @staticmethod
    def _classify(tiering: ClauseTiering,
                  queries: list[tuple[int, ...]]) -> np.ndarray:
        # batched ψ^clause via the clause-subset-test kernel — one call per
        # batch (the old per-query host path lives on as the test reference
        # in ClauseTiering.classify_queries)
        return matching.classify_batch(
            tiering.clause_vocab_bits, queries, tiering.vocab_size)

    def classify(self, queries: list[tuple[int, ...]]) -> np.ndarray:
        return self._classify(self._live.tiering, queries)

    def serve(self, queries: list[tuple[int, ...]]) -> list[np.ndarray]:
        """Returns the match set (sorted doc ids) per query."""
        live = self._live                    # one read: a consistent generation
        with obs.span("serve", n=len(queries), generation=live.generation):
            with obs.span("classify"):
                elig = self._classify(live.tiering, queries)
            toks = matching.pad_token_batch(queries)
            out: list[np.ndarray | None] = [None] * len(queries)
            w = self.postings_t2.shape[1]
            matched: list[tuple[np.ndarray, np.ndarray]] = []
            for tier, sel in ((1, elig), (2, ~elig)):
                idx = np.nonzero(sel)[0]
                if len(idx) == 0:
                    continue
                postings = live.postings_t1 if tier == 1 else self.postings_t2
                sub = jnp.asarray(toks[idx])
                with obs.span("t1_match" if tier == 1 else "t2_match",
                              n=int(len(idx))) as sp:
                    m = sp.fetch(sp.sync(matching.match_batch(postings, sub)))
                matched.append((idx, m))
                if tier == 1:
                    self.stats.n_tier1 += len(idx)
                    self.stats.tier1_words += \
                        len(idx) * live.tier1_words_per_query
                    _WORDS.inc(len(idx) * live.tier1_words_per_query,
                               tier="t1")
                else:
                    self.stats.tier2_words += len(idx) * w
                    _WORDS.inc(len(idx) * w, tier="t2")
            with obs.span("merge"):
                for idx, m in matched:
                    for row, qi in enumerate(idx):
                        out[qi] = bitset.np_to_indices(m[row], self.n_docs)
            self.stats.n_queries += len(queries)
            _QUERIES.inc(len(queries))
            _T1_HITS.inc(int(np.count_nonzero(elig)))
        return [o if o is not None else np.empty(0, np.int64) for o in out]

    def serve_reference(self, queries: list[tuple[int, ...]]) -> list[np.ndarray]:
        """Single-tier oracle for correctness tests."""
        toks = matching.pad_token_batch(queries)
        m = np.asarray(matching.match_batch(self.postings_t2, jnp.asarray(toks)))
        return [bitset.np_to_indices(r, self.n_docs) for r in m]
