"""Synthetic data: packed builders, preset stability, the bulk sampler.

The builders pack straight from (doc, term) pairs; the dense bool planes
they replaced are kept here as the reference. The small presets' outputs
are pinned by digest, so a change to the generator shows up as a failure
rather than as quietly different benchmark data.
"""
import hashlib

import numpy as np
import pytest

from repro.core import bitset
from repro.data import incidence, synthetic

# sha256 prefixes of the presets at seed 0 (mined at min_support 1e-3):
# doc tokens, doc bits, query log, queries, postings, clause-doc,
# clause-query and query-doc incidence
PINNED = {
    "tiny": ("8d80c70d49a1e015", "ca8024388d58b506", "b6e7b61c740c5afd",
             "533ce83dca4a02df", "669e7c92afa3b648", "397d4a435a3dce3c",
             "6534e55c1a6e6b20", "e4cdecd6b81e7cdd"),
    "small": ("e0fe4e2705e5c106", "76b58dcd82fdbc77", "3bf15c938d5d5e2d",
              "c7c7613d2873840f", "9375441569e1a791", "dc8f439f42a1a69d",
              "ee1d9a44ca460b04", "e601aa5640ea8c1d"),
}


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _text_digest(obj) -> str:
    return hashlib.sha256(repr(obj).encode()).hexdigest()[:16]


def _dense_pack(sets, n_items: int, transpose: bool = False) -> np.ndarray:
    bits = np.zeros((len(sets), n_items), dtype=bool)
    for i, s in enumerate(sets):
        bits[i, list(s)] = True
    return bitset.np_pack(bits.T if transpose else bits)


def _dense_and(postings, sets, n_bits):
    pad = bitset.np_pack(np.ones(n_bits, dtype=bool))
    out = []
    for s in sets:
        row = np.full(postings.shape[1], 0xFFFFFFFF, np.uint32)
        for t in s:
            row &= postings[t]
        out.append(row & pad)
    return np.stack(out)


@pytest.fixture(scope="module", params=["tiny", "small"])
def preset(request):
    corpus, log = synthetic.make_tiering_dataset(0, request.param)
    data = incidence.build_tiering_data(corpus, log, min_support=1e-3)
    return request.param, data


def test_packed_builders_equal_dense_reference(preset):
    _, data = preset
    corpus, log = data.corpus, data.log
    v = corpus.vocab_size
    np.testing.assert_array_equal(
        data.postings, _dense_pack(corpus.doc_tokens, v, transpose=True))
    np.testing.assert_array_equal(corpus.doc_bits,
                                  _dense_pack(corpus.doc_tokens, v))
    np.testing.assert_array_equal(log.query_bits, _dense_pack(log.queries, v))
    np.testing.assert_array_equal(
        data.clause_doc_bits,
        _dense_and(data.postings, data.clauses, corpus.n_docs))
    np.testing.assert_array_equal(
        data.query_doc_bits,
        _dense_and(data.postings, log.queries, corpus.n_docs))
    q_of_term = _dense_pack(log.queries, v, transpose=True)
    np.testing.assert_array_equal(
        data.clause_query_bits,
        _dense_and(q_of_term, data.clauses, log.n_queries))


def test_presets_are_bit_identical(preset):
    scale, data = preset
    corpus, log = data.corpus, data.log
    got = (_text_digest(corpus.doc_tokens), _digest(corpus.doc_bits),
           _digest(log.query_bits, log.train_weights, log.test_weights),
           _text_digest(log.queries), _digest(data.postings),
           _digest(data.clause_doc_bits), _digest(data.clause_query_bits),
           _digest(data.query_doc_bits))
    assert got == PINNED[scale]


def test_bulk_sampler_draws_the_same_law():
    """Bulk rounds keep each doc's length exactly (the lengths come from
    the shared RNG prefix) and its terms distinct; term frequencies match
    the per-doc `rng.choice` stream to within sampling noise."""
    corpora = [synthetic.make_corpus(np.random.default_rng(3), vocab_size=512,
                                     n_docs=20000, doc_len_mean=6.0,
                                     bulk=bulk) for bulk in (False, True)]
    lengths = [[len(d) for d in c.doc_tokens] for c in corpora]
    assert lengths[0] == lengths[1]
    assert all(list(d) == sorted(set(d)) for d in corpora[1].doc_tokens)
    freq = []
    for c in corpora:
        f = np.bincount([t for d in c.doc_tokens for t in d], minlength=512)
        freq.append(f / f.sum())
    assert np.abs(freq[0] - freq[1]).sum() < 0.05


def test_passage_preset_shape():
    corpus, log = synthetic.make_tiering_dataset(0, "passage", n_docs=4096)
    assert corpus.vocab_size == 8192 and corpus.n_docs == 4096
    assert 8000 < log.n_queries <= 8192
    assert all(list(d) == sorted(set(d)) and len(d) >= 2
               for d in corpus.doc_tokens)
    np.testing.assert_array_equal(
        incidence.build_postings(corpus),
        _dense_pack(corpus.doc_tokens, 8192, transpose=True))
