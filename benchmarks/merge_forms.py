"""Host time of the router's merge: one match row of packed words -> doc ids.

Rows are the answers of queries drawn by test popularity from the corpus
that `benchmarks/chip/corpus.make` builds for a configuration: the AND of
each query's terms' postings rows (`reference.Postings`), as the router
receives them. Each row goes through the old form (unpack all the row's
bits, then `np.nonzero`) and the kept form (`bitset.np_to_indices`), in
turn, and the two outputs must be bit-identical.

    PYTHONPATH=src python3 benchmarks/merge_forms.py \\
        benchmarks/chip/configs/msmarco-passage-q1.json 3915000052 1000

prints a `ROWS` line (answer sizes and nonzero words a row) and a `FORM`
line a form with the mean, median and p99 ms a row. The configuration at
full size packs a 2 GiB postings matrix on the host.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chip"))
import corpus  # noqa: E402
import reference  # noqa: E402

from repro.core import bitset  # noqa: E402


def old_form(words: np.ndarray, n_bits: int) -> np.ndarray:
    """Every bit of the row unpacked, then `np.nonzero` over all of them."""
    return np.nonzero(bitset.np_unpack(words, n_bits))[-1]


FORMS = {"old": old_form, "kept": bitset.np_to_indices}


def rows(cfg: dict, seed: int, n_rows: int) -> tuple[list[np.ndarray], int]:
    data = corpus.make(cfg, seed)
    post = reference.Postings(data.doc, data.term, data.n_docs,
                              data.vocab_size)
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(data.queries), n_rows, p=data.test_weights)
    return [post.row_and(data.queries[i]) for i in pick], data.n_docs


def _stats(ms: list[float]) -> dict:
    return {"mean_ms": float(np.mean(ms)), "median_ms": float(np.median(ms)),
            "p99_ms": float(np.percentile(ms, 99))}


def main(cfg_path: str, seed: int, n_rows: int) -> dict:
    with open(cfg_path) as f:
        cfg = json.load(f)
    answers, n_docs = rows(cfg, seed, n_rows)
    sizes = [int(bitset.np_popcount(r)) for r in answers]
    nz = [int(np.count_nonzero(r)) for r in answers]
    print("ROWS", json.dumps({
        "rows": n_rows, "words_per_row": len(answers[0]),
        "ids_median": float(np.median(sizes)), "ids_mean": float(np.mean(sizes)),
        "ids_p99": float(np.percentile(sizes, 99)),
        "nz_words_median": float(np.median(nz)),
        "nz_words_mean": float(np.mean(nz))}), flush=True)
    times: dict[str, list[float]] = {name: [] for name in FORMS}
    for row in answers:
        outs = []
        for name, form in FORMS.items():
            t0 = time.perf_counter()
            outs.append(form(row, n_docs))
            times[name].append((time.perf_counter() - t0) * 1e3)
        first = outs[0]
        for out in outs[1:]:
            assert out.dtype == first.dtype and np.array_equal(out, first)
    result = {name: _stats(ms) for name, ms in times.items()}
    for name, row in result.items():
        print("FORM", json.dumps({"form": name, **row}), flush=True)
    return result


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
