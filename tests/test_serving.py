"""Two-tier serving engine: end-to-end correctness vs single-tier oracle,
plus the zero-downtime re-tiering surface (swap_tiering, ServeStats
reset/merge) the streaming control loop rides on."""
import numpy as np
import pytest

from repro.core import SOLVERS
from repro.core.tiering import ClauseTiering
from repro.serve.engine import ServeStats, TieredEngine


def _engine(tiny_data, tiny_problem):
    r = SOLVERS["optpes"](tiny_problem, tiny_data.n_docs // 2)
    tiering = ClauseTiering.from_selection(tiny_data, r.selected)
    return TieredEngine(tiny_data.postings, tiering, tiny_data.n_docs)


def test_served_match_sets_are_complete(tiny_data, tiny_problem):
    engine = _engine(tiny_data, tiny_problem)
    rng = np.random.default_rng(0)
    qidx = rng.choice(tiny_data.n_queries, size=64, replace=False)
    queries = [tiny_data.log.queries[i] for i in qidx]
    got = engine.serve(queries)
    want = engine.serve_reference(queries)
    for q, a, b in zip(queries, got, want):
        np.testing.assert_array_equal(a, b, err_msg=str(q))


def test_engine_routes_and_saves_cost(tiny_data, tiny_problem):
    engine = _engine(tiny_data, tiny_problem)
    queries = [tiny_data.log.queries[i] for i in range(200)]
    engine.serve(queries)
    s = engine.stats
    assert s.n_queries == 200
    assert 0 < s.n_tier1 < 200          # both tiers exercised
    assert s.cost_saving > 0.0          # tiering actually saves traffic


def test_swap_tiering_parity_every_generation(tiny_data, tiny_problem):
    """Theorem 3.1 must hold before AND after a hot swap: every eligible
    query's Tier-1 result set equals single-tier matching."""
    engine = _engine(tiny_data, tiny_problem)
    queries = [tiny_data.log.queries[i] for i in range(128)]

    def assert_parity():
        got = engine.serve(queries)
        want = engine.serve_reference(queries)
        for q, a, b in zip(queries, got, want):
            np.testing.assert_array_equal(a, b, err_msg=str(q))

    assert engine.generation == 0
    assert_parity()
    # re-tier to a different (smaller-budget) clause selection and swap
    r2 = SOLVERS["optpes"](tiny_problem, tiny_data.n_docs // 4)
    t2 = ClauseTiering.from_selection(tiny_data, r2.selected)
    buf = engine.prepare_tiering(t2)          # built off the request path
    assert engine.tiering is not t2           # still serving the old gen
    assert engine.swap_tiering(buf) == 1
    assert engine.tiering is t2
    assert_parity()
    # raw-ClauseTiering swap path (prepare happens inside)
    r3 = SOLVERS["greedy"](tiny_problem, tiny_data.n_docs // 2)
    assert engine.swap_tiering(
        ClauseTiering.from_selection(tiny_data, r3.selected)) == 2
    assert_parity()


def test_swap_changes_routing_but_stats_merge(tiny_data, tiny_problem):
    """Per-window stats around a swap must merge into the cumulative total."""
    engine = _engine(tiny_data, tiny_problem)
    queries = [tiny_data.log.queries[i] for i in range(150)]

    engine.stats.reset()
    engine.serve(queries)
    before = engine.stats.snapshot()

    r2 = SOLVERS["optpes"](tiny_problem, tiny_data.n_docs // 4)
    engine.swap_tiering(ClauseTiering.from_selection(tiny_data, r2.selected))
    engine.stats.reset()
    engine.serve(queries)
    after = engine.stats.snapshot()

    # the quarter-budget tiering routes fewer queries to Tier 1
    assert after.n_tier1 < before.n_tier1

    total = ServeStats()
    total.merge(before).merge(after)
    assert total.n_queries == 300
    assert total.n_tier1 == before.n_tier1 + after.n_tier1
    assert total.tier1_words == before.tier1_words + after.tier1_words
    assert total.tier2_words == before.tier2_words + after.tier2_words
    assert total.full_words_per_query == before.full_words_per_query
    assert 0.0 < total.cost_saving < 1.0


def test_stats_reset_and_merge_guard():
    s = ServeStats(n_queries=5, n_tier1=3, tier1_words=10, tier2_words=20,
                   full_words_per_query=7)
    s.reset()
    assert (s.n_queries, s.n_tier1, s.tier1_words, s.tier2_words) == \
        (0, 0, 0, 0)
    assert s.full_words_per_query == 7      # engine constant survives reset
    with pytest.raises(ValueError, match="postings widths"):
        s.merge(ServeStats(full_words_per_query=9))


def test_unseen_query_with_known_clause_is_eligible(tiny_data, tiny_problem):
    """The paper's central generalization property, end to end: a query never
    seen in any log is still served by Tier 1 when it contains a selected
    clause."""
    engine = _engine(tiny_data, tiny_problem)
    clause = engine.tiering.clauses[0]
    novel_query = tuple(sorted(set(clause) | {int(c) + 1 for c in clause[:1]}))
    elig = engine.classify([novel_query, (63,)])
    assert elig[0]
    got = engine.serve([novel_query])
    want = engine.serve_reference([novel_query])
    np.testing.assert_array_equal(got[0], want[0])


# -- match_batch: the row fetch against a numpy AND of the named rows --------

def _and_of_rows(postings: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    out = np.full((tokens.shape[0], postings.shape[1]), 0xFFFFFFFF, np.uint32)
    for i, row in enumerate(tokens):
        for t in row[row >= 0]:
            out[i] &= postings[t]
    return out


@pytest.mark.parametrize("l", [1, 8])
@pytest.mark.parametrize("b", [1, 32])
@pytest.mark.parametrize("w", [1, 128, 32768, 65536])
def test_match_batch_is_and_of_named_rows(w, b, l):
    """Bit-identical to an AND of the named postings rows, at widths from
    one word to a passage shard's 65,536, with -1 padding, an all-padding
    query and repeated tokens (13 rows repeat tokens within and across
    queries)."""
    import jax.numpy as jnp
    from repro.serve import matching
    rng = np.random.default_rng(w * 100 + b * 10 + l)
    v = 13
    postings = rng.integers(0, 2 ** 32, (v, w), dtype=np.uint32)
    tokens = rng.integers(0, v, (b, l)).astype(np.int32)
    lens = rng.integers(1, l + 1, b)
    tokens[np.arange(l)[None, :] >= lens[:, None]] = -1
    if b > 1:
        tokens[0] = -1                         # all padding: all-ones row
        tokens[1] = tokens[2]                  # one query twice
    if l > 1:
        tokens[-1, :2] = tokens[-1, 0]         # one term twice in a query
    got = np.asarray(matching.match_batch(jnp.asarray(postings),
                                          jnp.asarray(tokens)))
    assert got.dtype == np.uint32 and got.shape == (b, w)
    np.testing.assert_array_equal(got, _and_of_rows(postings, tokens))
    if b > 1:
        assert (got[0] == 0xFFFFFFFF).all()
