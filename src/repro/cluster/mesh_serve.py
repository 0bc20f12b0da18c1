"""Fused scatter-gather serving: one shard_map program per batch.

The host router issues one sequential dispatch per shard per batch; on a
mesh the shards ARE devices, so the whole serve path fuses into a single
SPMD program over the `"shard"` axis:

  1. replicated classify — every device runs the packed clause-subset-test
     kernel (`ops.clause_match`) on the full batch, so the ψ^clause decision
     needs no broadcast;
  2. scatter — each query's work lands on the devices that own its doc
     words: the device holds its shard's RESIDENT postings as ONE stacked
     tier matrix (`tiers[s, 0]` = Tier-2, `tiers[s, 1]` = Tier-1) and the
     shared `fused_match.select_rows_match` core turns ψ's per-query tier
     choice into gather index arithmetic — one postings row fetched per
     (query, token), half the gather traffic of the old fetch-both-then-
     `where` schedule;
  3. gather — shards own disjoint word ranges, so the OR-merge is a
     `ppermute` ring: each step every device ships only its LOCAL [S_loc, B,
     wmax] match block to its ring neighbor and ORs the block it received
     into the owned word range (read-modify-write, so a narrow shard's zero
     tail never clobbers a neighbor's words). Wire bytes per device-step are
     `B * wmax * S_loc` — the owned slice — instead of the full-width
     `B * W_total` the old `psum` shipped, a ~`n_devices`× reduction (see
     ROADMAP "ring-merge wire model"). OR of disjoint contributions equals
     the integer psum it replaces, so the output is bit-identical.

Bit-identity with the host path is by construction: the classify kernel, the
AND-reduce, and the word placement are the same ops on the same bits — only
the dispatch moves. Parity at every shard/replica count is pinned by
tests/test_mesh.py (replicas don't enter: replicas of a shard hold identical
content, which is exactly what lets the mesh hold one copy per shard).

Operands live in a `MeshRouteTable`: per-shard slices are zero-padded to the
widest shard and stacked leading-axis-sharded over `"shard"` (pad shards
write zeros into a scratch word range past the real index, so they never
touch owned words). Tables are built once per (generation content, CORPUS
VERSION, topology) — a corpus append invalidates by key, and the table's
Tier-2 slices come from the buffer's pinned snapshot rather than the live
replicas, so a mid-roll replica can never leak a mixed-version slice into
the fused path. Batches are bucketed to powers of two and, past
`_PIPE_CHUNK` queries, split into chunks whose dispatches are all issued
before any result is awaited — the host packs and classifies chunk i+1
while the mesh is still AND-matching chunk i.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import distributed
from repro.kernels import fused_match
from repro.kernels import ops
from repro.serve import matching

ONES = 0xFFFFFFFF


@dataclasses.dataclass(frozen=True)
class MeshRouteTable:
    """Device-resident operands of the fused serve program for ONE
    (ψ generation, fleet topology) pair. `S'` is the shard count padded to a
    multiple of the `"shard"` axis size; `wmax` the widest shard's words."""
    clause_bits: jnp.ndarray   # uint32 [K, Wv]  ψ clauses (replicated)
    tiers: jnp.ndarray         # uint32 [S', 2, V, wmax]  resident slices
    #                            (index 0: Tier-2, index 1: Tier-1)
    off: jnp.ndarray           # int32 [S'] owned word_lo (pad rows: w_total)
    wid: jnp.ndarray           # int32 [S'] owned words (pad rows: 0)
    t1w: jnp.ndarray           # int32 [S'] compacted Tier-1 words (0: no D₁)
    w_total: int               # global packed match-set width
    wmax: int
    vocab_size: int


def _shards_per_device(n_shards: int, n_devices: int) -> int:
    return -(-n_shards // n_devices)


def shard_device(plan, shard_index: int, n_shards: int):
    """The device whose block of the fused route table holds shard
    `shard_index` (None off a multi-device `"shard"` mesh). The table is
    sharded by leading-axis blocks of `ceil(n_shards / n_devices)` shards."""
    if not plan.shard_fused:
        return None
    pos = shard_index // _shards_per_device(n_shards, plan.n_shard_devices)
    axis = plan.mesh.axis_names.index(plan.shard_axis)
    return np.moveaxis(plan.mesh.devices, axis, 0)[pos:pos + 1].flat[0]


def _tier_block(buf, lo: int, hi: int, v: int, wmax: int, use_t1: bool,
                device) -> jnp.ndarray:
    """uint32 [hi - lo, 2, V, wmax]: stacked (Tier-2, Tier-1) slices of
    table rows [lo, hi), built on `device` from the buffer's own arrays."""
    def place(x):
        if x is None:
            return jnp.zeros((v, wmax), jnp.uint32, device=device)
        x = jax.device_put(x, device)
        return x if x.shape[1] == wmax else \
            jnp.pad(x, ((0, 0), (0, wmax - x.shape[1])))

    rows = []
    for i in range(lo, hi):
        s = buf.shards[i] if i < len(buf.shards) else None   # pad shard
        rows.append(place(None if s is None else buf.t2_postings[s.index]))
        rows.append(place(buf.shard_postings[s.index]
                          if s is not None and use_t1 else None))
    return jnp.stack(rows).reshape(hi - lo, 2, v, wmax)


def build_table(buf, plan, *, use_t1: bool = True) -> MeshRouteTable:
    """Stack per-shard resident slices for the fused program.

    Every operand comes from ONE `ClusterTieringBuffer`: its Tier-1
    sub-indexes (the SAME bits a committed replica holds) and its pinned
    corpus snapshot — shard plan, Tier-2 slices, global width — so a table
    can never pair tiers from different corpus versions (repro.ingest).
    With `use_t1=False` (the mid-rollout gap, served entirely at the
    buffer's corpus version) the ψ clause set is empty and every query
    routes to the buffer's Tier-2 slices, still one fused dispatch.

    Each device's block is stacked on that device from the slices placed
    there (`shard_device`), so no device ever holds the whole index.
    """
    from jax.sharding import NamedSharding

    shards = buf.shards
    mesh, axis = plan.mesh, plan.shard_axis
    vocab_size = buf.tiering.vocab_size
    wmax = max(s.n_words for s in shards)
    s_all = _shards_per_device(len(shards), plan.n_shard_devices) \
        * plan.n_shard_devices
    v = int(buf.t2_postings[0].shape[0])
    off, wid, t1w = [], [], []
    for s in shards:
        t1w.append(buf.shard_words[s.index] if use_t1 else 0)
        off.append(s.word_lo)
        wid.append(s.n_words)
    for _ in range(s_all - len(shards)):   # pad shards: zero words, scratch
        off.append(buf.w_total)            # offset past the real index
        wid.append(0)
        t1w.append(0)
    shape = (s_all, 2, v, wmax)
    sharded = NamedSharding(mesh, P(axis))
    blocks = [_tier_block(buf, idx[0].start or 0, idx[0].stop or s_all, v,
                          wmax, use_t1, dev)
              for dev, idx in
              sharded.addressable_devices_indices_map(shape).items()]
    cbits = buf.tiering.clause_vocab_bits if use_t1 else \
        np.zeros((0, max(1, -(-vocab_size // 32))), np.uint32)

    def put(x, spec):
        return jax.device_put(np.asarray(x), NamedSharding(mesh, spec))

    return MeshRouteTable(
        clause_bits=put(cbits, P()),
        tiers=jax.make_array_from_single_device_arrays(shape, sharded,
                                                       blocks),
        off=put(np.asarray(off, np.int32), P(axis)),
        wid=put(np.asarray(wid, np.int32), P(axis)),
        t1w=put(np.asarray(t1w, np.int32), P(axis)),
        w_total=buf.w_total, wmax=wmax, vocab_size=vocab_size)


_PROGRAMS: dict = {}


def _program(mesh, axis: str, w_total: int, wmax: int, n_clauses: int):
    """The compiled fused program for one (mesh, widths, ψ size) signature."""
    key = (mesh, axis, w_total, wmax, n_clauses > 0)
    if key in _PROGRAMS:
        return _PROGRAMS[key]

    n_dev = mesh.shape[axis]

    def body(qbits, cbits, toks, tiers, off, wid, t1w):
        elig = ops.clause_match(qbits, cbits)              # replicated [B]
        cols = jnp.arange(wmax, dtype=jnp.int32)
        b = toks.shape[0]
        s_loc, _, v, _ = tiers.shape                       # local shards
        blocks = []
        for i in range(s_loc):
            # owner-local AND-match: ψ picks the resident tier per query via
            # the stacked-gather core (one row fetch per query token)
            m = fused_match.select_rows_match(
                tiers[i].reshape(2 * v, wmax), v,
                elig & (t1w[i] > 0), toks)
            # host parity: the router never contacts a shard whose local D₁
            # is empty for an eligible query — its words stay zero
            m = jnp.where(elig[:, None] & (t1w[i] == 0), jnp.uint32(0), m)
            m = jnp.where(cols[None, :] < wid[i], m, jnp.uint32(0))
            blocks.append(m)
        blk = jnp.stack(blocks)                            # [S_loc, B, wmax]

        out = jnp.zeros((b, w_total + wmax), jnp.uint32)

        def scatter(out, blk, offs):
            # read-OR-write: a narrow shard's zero tail (wid < wmax) lands on
            # a neighbor's owned words and must not overwrite them
            for i in range(s_loc):
                cur = jax.lax.dynamic_slice(out, (0, offs[i]), (b, wmax))
                out = jax.lax.dynamic_update_slice(out, cur | blk[i],
                                                   (0, offs[i]))
            return out

        out = scatter(out, blk, off)
        # ring OR-merge: circulate each device's owned match block around the
        # ring; after n_dev-1 hops every device has OR'd every shard's
        # contribution, replicating the full match set (disjoint OR == the
        # integer psum this replaces) at 1/n_dev the per-step wire bytes.
        perm = [(d, (d + 1) % n_dev) for d in range(n_dev)]
        for _ in range(n_dev - 1):
            blk = jax.lax.ppermute(blk, axis, perm)
            off = jax.lax.ppermute(off, axis, perm)
            out = scatter(out, blk, off)
        return out, elig

    fused = distributed.mesh_fused(
        body,
        in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()), axis=axis, mesh=mesh)
    prog = jax.jit(fused)
    if len(_PROGRAMS) > 32:
        _PROGRAMS.clear()
    _PROGRAMS[key] = prog
    return prog


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


_PIPE_CHUNK = 512


def serve_fused(table: MeshRouteTable, queries, plan
                ) -> tuple[np.ndarray, np.ndarray]:
    """Serve one batch through the fused program.

    Returns `(match_words [B, w_total] uint32, eligible [B] bool)` —
    bit-identical to the host router's scatter-gather OR-merge. Batch and
    token dims are bucketed to powers of two (padded queries are empty and
    sliced off) so the program compiles once per bucket, not per batch.
    Batches past `_PIPE_CHUNK` are split into chunks and every chunk's
    dispatch is issued before any result is awaited: JAX's async dispatch
    overlaps the host-side pack+classify of chunk i+1 with the device-side
    AND-match of chunk i.
    """
    b = len(queries)
    lb = _bucket(max((len(q) for q in queries), default=1))
    wv = max(1, -(-table.vocab_size // 32))
    prog = _program(plan.mesh, plan.shard_axis, table.w_total, table.wmax,
                    int(table.clause_bits.shape[0]))
    spans = [(lo, min(lo + _PIPE_CHUNK, b))
             for lo in range(0, max(b, 1), _PIPE_CHUNK)]
    pending = []
    for lo, hi in spans:
        sub = list(queries[lo:hi])
        bb = _bucket(hi - lo)
        toks = np.full((bb, lb), -1, np.int32)
        toks[:hi - lo] = matching.pad_token_batch(sub, pad_len=lb)
        qbits = np.zeros((bb, wv), np.uint32)
        if table.clause_bits.shape[0] and sub:
            qbits[:hi - lo] = matching.pack_query_bits(sub, table.vocab_size)
        pending.append(prog(jnp.asarray(qbits), table.clause_bits,
                            jnp.asarray(toks), table.tiers,
                            table.off, table.wid, table.t1w))
    match = np.concatenate([np.asarray(o[:hi - lo, :table.w_total])
                            for (lo, hi), (o, _) in zip(spans, pending)])
    elig = np.concatenate([np.asarray(e[:hi - lo]).astype(bool)
                           for (lo, hi), (_, e) in zip(spans, pending)])
    return match, elig
