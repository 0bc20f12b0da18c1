"""Device time of row-fetch forms of `match_batch` on one chip.

Each (form, width, batch) is its own jitted program with a unique name,
so one profiler trace gives each program's device time. Postings are
random `[8192, W]` words, L = 8 term slots with 1-8 terms a query and,
above batch 2, one query of padding alone. Every form's answer is checked
against the first form's.

    PYTHONPATH=src python3 benchmarks/match_forms.py \\
        gather,a,b,c,d,e,u 65536,32768 1,11,32 200

prints a `DEV` line a program with `device_us_per_q` (device time a
query) and `same` (bit-identical to the first form).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chip"))
import trace_reduce  # noqa: E402

from repro.serve import matching  # noqa: E402

ONES = jnp.uint32(0xFFFFFFFF)
V, L = 8192, 8


def _and(rows, valid):
    rows = jnp.where(valid[..., None], rows, ONES)
    return lax.reduce(rows, ONES, lax.bitwise_and, (1,))


def gather(postings, tokens):
    """One XLA gather of [B, L] rows: copies postings wider than 32,768."""
    valid = tokens >= 0
    return _and(postings[jnp.where(valid, tokens, 0)], valid)


def form_b(postings, tokens):
    """A vmap-ed dynamic_slice over every (query, term), one AND-reduce."""
    fetch = jax.vmap(jax.vmap(matching._row, (None, 0)), (None, 0))
    return _and(fetch(postings, tokens), tokens >= 0)


def form_c(postings, tokens, split=2):
    """One lax.gather of `split` column slices a row."""
    (b, l), w = tokens.shape, postings.shape[1]
    s = w // split
    valid = tokens >= 0
    safe = jnp.where(valid, tokens, 0)
    starts = jnp.stack(jnp.broadcast_arrays(
        safe[..., None], (jnp.arange(split) * s)[None, None, :]), -1)
    dn = lax.GatherDimensionNumbers(offset_dims=(3,), collapsed_slice_dims=(0,),
                                    start_index_map=(0, 1))
    rows = lax.gather(postings, starts, dn, slice_sizes=(1, s),
                      mode="promise_in_bounds").reshape(b, l, w)
    return _and(rows, valid)


def form_d(postings, tokens):
    """A fori_loop over (query, term), read-modify-write of the query row."""
    b, l = tokens.shape

    def body(i, acc):
        q, t = i // l, tokens[i // l, i % l]
        row = lax.dynamic_slice_in_dim(postings, jnp.maximum(t, 0), 1)
        row = jnp.where(t >= 0, row, ONES)
        cur = lax.dynamic_slice_in_dim(acc, q, 1)
        return lax.dynamic_update_slice_in_dim(acc, cur & row, q, 0)
    return lax.fori_loop(0, b * l, body,
                         jnp.full((b, postings.shape[1]), ONES))


def form_e(postings, tokens, split=2):
    """One lax.gather a column slice, results concatenated."""
    (b, l), w = tokens.shape, postings.shape[1]
    s = w // split
    valid = tokens >= 0
    safe = jnp.where(valid, tokens, 0).reshape(-1)
    dn = lax.GatherDimensionNumbers(offset_dims=(1,), collapsed_slice_dims=(0,),
                                    start_index_map=(0, 1))
    return jnp.concatenate([_and(lax.gather(
        postings, jnp.stack([safe, jnp.full_like(safe, j * s)], -1), dn,
        slice_sizes=(1, s), mode="promise_in_bounds").reshape(b, l, s), valid)
        for j in range(split)], 1)


def form_u(postings, tokens):
    """One dynamic_slice op a (query, term), unrolled."""
    b, l = tokens.shape
    safe = jnp.maximum(tokens, 0)
    rows = jnp.stack([jnp.concatenate(
        [lax.dynamic_slice_in_dim(postings, safe[q, t], 1)
         for t in range(l)]) for q in range(b)])
    return _and(rows, tokens >= 0)


# a: a loop over term slots, each fetching B rows, ANDed into [B, W].
FORMS = {"gather": gather, "a": matching.match_batch.__wrapped__, "b": form_b,
         "c": form_c, "d": form_d, "e": form_e, "u": form_u}


def _named(fn, name):
    def prog(postings, tokens):
        return fn(postings, tokens)
    prog.__name__ = prog.__qualname__ = name
    return jax.jit(prog)


def _tokens(rng, b):
    lens = rng.integers(1, L + 1, b)
    t = np.full((b, L), -1, np.int32)
    for i, n in enumerate(lens):
        t[i, :n] = rng.integers(0, V, n)
    if b > 2:
        t[0] = -1
    return jnp.asarray(t)


def main(forms, widths, batches, n_calls=200):
    print("DEVICE", jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(7)
    for w in widths:
        postings = jax.random.bits(jax.random.key(w), (V, w), jnp.uint32)
        progs = {}
        for b in batches:
            toks = [_tokens(rng, b) for _ in range(16)]
            first = None
            for f in forms:
                name = f"mf_{f}_{w}_{b}x"
                prog = _named(FORMS[f], name)
                t0 = time.perf_counter()
                got = np.asarray(prog(postings, toks[0]))
                compile_s = time.perf_counter() - t0
                first = got if first is None else first
                progs[name] = (prog, toks, {
                    "form": f, "w": w, "b": b, "compile_s": round(compile_s, 2),
                    "same": bool((got == first).all())})
                for i in range(n_calls // 4):
                    out = prog(postings, toks[i % 16])
                out.block_until_ready()
        trace_dir = tempfile.mkdtemp()
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            for prog, toks, _ in progs.values():
                for i in range(n_calls):
                    out = prog(postings, toks[i % 16])
                out.block_until_ready()
        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        for name, (_, _, row) in progs.items():
            s, n = trace_reduce.program_s(trace, name)
            row["device_us_per_q"] = round(s * 1e6 / max(n, 1) / row["b"], 2)
            row["events"] = n
            print("DEV", json.dumps(row), flush=True)
        del postings


if __name__ == "__main__":
    args = sys.argv[1:]
    main(args[0].split(","), [int(w) for w in args[1].split(",")],
         [int(b) for b in args[2].split(",")],
         *(int(a) for a in args[3:4]))
