"""Vectorized hash join — nodeHashjoin.c reimagined for static shapes.

Sort-based build (round-2 redesign): build rows are sorted ONCE by
(bucket, exact key columns) with ``lax.sort``'s multi-operand lexicographic
mode, so rows with equal keys form contiguous *runs* inside their hash
bucket. The table is then just the sorted arrays plus CSR bucket offsets:

  build = 1 stable sort + 1 scatter-add (bucket counts) + cumulative scans
  probe = hop run-head to run-head inside the bucket (dynamic-trip
          ``while_loop``; each hop is one gather per key column)

This replaces the round-1 open-addressing claim loop whose per-round
full-table scatters cost ~30s at 15M build rows on v5e; the sort build is
two orders of magnitude cheaper and needs no slot-claim conflict rounds at
all. Duplicate build keys are first-class: a probe hit lands on its run's
head and reads the run length, so unique joins (winner = first build row),
multi-match CSR expansion, and duplicate detection (any run length > 1)
all fall out of the same structure.

A probe keeps the probe side's capacity: each probe row gains a ``matched``
flag and a gathered build-row index, so inner/left/semi/anti joins are all
selection-mask updates plus gathers — no dynamic-size compaction here (the
compiler may compact an inner join's matches to a static fraction of the
slots before ``gather_build_columns``).

SQL NULL semantics: a NULL join key equals nothing, so NULL-keyed rows on
either side never participate (they sort to the dead tail past every live
bucket). Float keys are canonicalized (-0.0 -> 0.0) before sorting so SQL
equality matches run grouping; NaN != NaN falls out of IEEE compare.

Reference parity: src/backend/executor/nodeHashjoin.c + nodeHash.c roles
(hash build/probe, duplicate chains); the CSR expansion stands in for the
dynamic output batching under XLA's static shapes.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from greengage_tpu.ops import hashing
from greengage_tpu.ops.agg import BIG, KeySpec


def _canon_values(k: KeySpec):
    """Key values under SQL equality: canonicalize float zeros."""
    v = k.values
    if jnp.issubdtype(v.dtype, jnp.floating):
        v = jnp.where(v == 0.0, jnp.zeros((), v.dtype), v)
    return v


def _bucket_hash(keys: list[KeySpec]) -> jnp.ndarray:
    """uint32 bucket hash over the key columns.

    Joins only need build and probe to agree (probe TEXT codes are already
    translated into the build's code space by the binder), so every column
    — TEXT codes included — hashes as its integer representation; no
    dictionary LUT is needed here, unlike distribution hashing.
    """
    hs = []
    for k in keys:
        v = _canon_values(k)
        if jnp.issubdtype(v.dtype, jnp.floating):
            v = v.view(jnp.int64 if v.dtype == jnp.float64 else jnp.int32)
        hs.append(hashing.hash_i64(v))
    return hashing.row_hash(hs)


def join_pack_bits(bounds: list | None) -> int | None:
    """Total bits to pack join-key tuples with per-key (lo, hi) integer
    bounds. NULL keys never participate in joins (strict selection), so no
    NULL slot is reserved — unlike agg.pack_bits. None = not packable."""
    if not bounds or any(b is None for b in bounds):
        return None
    total = 0
    for lo, hi in bounds:
        span = int(hi) - int(lo) + 1
        if span <= 0:
            return None
        total += max((span - 1).bit_length(), 1)
        if total > 64:
            return None
    return total


def pack_join_keys(keys: list[KeySpec], bounds: list):
    """Pack key columns into one uint32/uint64 word per row using the
    BUILD side's ANALYZE bounds. -> (word, in_bounds): rows whose values
    fall outside the bounds get in_bounds=False — on the build side that
    is a stats-staleness violation (caller flags + retries unpacked); on
    the probe side such a row simply cannot match any build key.

    Why: the probe walk gathers one key column per hop per key — packing
    makes that ONE u32 gather (measured 64ms vs 136ms per 6M-row gather
    for i32 vs i64), and the build sort drops to a single key operand."""
    total = join_pack_bits(bounds)
    dtype = jnp.uint32 if total <= 32 else jnp.uint64
    n = keys[0].values.shape[0]
    word = jnp.zeros((n,), dtype)
    in_bounds = jnp.ones((n,), bool)
    for k, (lo, hi) in zip(keys, bounds):
        span = int(hi) - int(lo) + 1
        width = max((span - 1).bit_length(), 1)
        v = _canon_values(k).astype(jnp.int64)
        ok = (v >= lo) & (v <= hi)
        in_bounds = in_bounds & ok
        field = jnp.where(ok, v - jnp.int64(lo), 0).astype(dtype)
        word = (word << dtype(width)) | field
    return word, in_bounds


@dataclass
class SortTable:
    """Sorted-run join table (see module docstring).

    Arrays live at *sorted position* granularity except ``starts``/
    ``counts`` (bucket granularity). ``next_head[i]`` is the smallest
    run-head position >= i (BIG past the last run) — the probe walk's hop
    pointer. ``n_live`` is the number of participating build rows (the dead
    tail starts there)."""

    keys_sorted: list[jnp.ndarray]
    rows_sorted: jnp.ndarray       # int32 [n] build row index per position
    next_head: jnp.ndarray        # int32 [n]
    starts: jnp.ndarray            # int32 [M] first position of bucket
    counts: jnp.ndarray            # int32 [M] live rows in bucket
    n_live: jnp.ndarray            # int32 scalar
    overflow: jnp.ndarray          # bool scalar: probe walk bound exceeded
    dup: jnp.ndarray               # bool scalar: duplicate build keys
    size: int
    # packed mode: keys_sorted is ONE u32/u64 word column; the probe must
    # apply the same packing (bounds) — build-side out-of-bounds values
    # raise pack_viol (stale stats -> caller re-runs unpacked)
    bounds: list | None = None
    pack_viol: jnp.ndarray | None = None
    # bool [n]: the sorted position heads a run of equal keys (live only);
    # a semi join counts its build's distinct keys from it
    head: jnp.ndarray | None = None

    @property
    def base(self) -> "SortTable":
        # multi-match call sites read table.base.overflow; the sorted table
        # serves both roles, so base is identity
        return self


def build(keys: list[KeySpec], sel, table_size: int, num_probes: int,
          key_bounds: list | None = None) -> SortTable:
    """Build the sorted-run table. ``num_probes`` is unused at build time
    (kept for call-site compatibility; the probe walk takes its own bound).
    ``key_bounds`` (build-side ANALYZE (lo, hi) per key) switches to the
    packed single-word key representation."""
    from jax import lax

    M = table_size
    assert M & (M - 1) == 0
    n = sel.shape[0]
    strict = sel
    for k in keys:
        if k.valid is not None:
            strict = strict & k.valid   # NULL keys never participate
    h = _bucket_hash(keys)
    pack_viol = None
    bounds = None
    if key_bounds is not None and join_pack_bits(key_bounds) is not None:
        word, in_b = pack_join_keys(keys, key_bounds)
        pack_viol = jnp.any(strict & ~in_b)
        # keep the table well-formed even when the flag fires (the run's
        # result is discarded): out-of-bounds rows drop from the table
        strict = strict & in_b
        kvals = [word]
        bounds = key_bounds
    else:
        kvals = [_canon_values(k) for k in keys]
    slot = jnp.where(strict, (h & jnp.uint32(M - 1)).astype(jnp.int32), M)
    row_idx = jnp.arange(n, dtype=jnp.int32)
    sorted_ops = lax.sort(
        tuple([slot] + kvals + [row_idx]), num_keys=1 + len(kvals),
        is_stable=True)
    slot_s = sorted_ops[0]
    keys_s = list(sorted_ops[1:-1])
    rows_s = sorted_ops[-1]
    live_s = slot_s < M

    counts = jnp.zeros((M + 1,), jnp.int32).at[slot].add(
        jnp.where(strict, 1, 0))[:M]
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)])

    # run heads: first position of each contiguous equal-key run. A bucket
    # boundary always starts a run (equal keys always share a bucket).
    same_prev = slot_s[1:] == slot_s[:-1]
    for ks in keys_s:
        same_prev = same_prev & (ks[1:] == ks[:-1])
    head = jnp.concatenate([jnp.ones((min(n, 1),), bool), ~same_prev]) \
        if n > 1 else jnp.ones((n,), bool)
    head = head & live_s
    dup = jnp.any(live_s & ~head)

    next_head = lax.cummin(
        jnp.where(head, jnp.arange(n, dtype=jnp.int32), BIG), axis=0,
        reverse=True)
    return SortTable(
        keys_sorted=keys_s, rows_sorted=rows_s, next_head=next_head,
        starts=starts, counts=counts,
        n_live=jnp.sum(strict.astype(jnp.int32)),
        overflow=jnp.zeros((), bool), dup=dup, size=M,
        bounds=bounds, pack_viol=pack_viol, head=head)


def _walk(table: SortTable, keys: list[KeySpec], sel, num_probes: int):
    """Hop the probe's bucket run-head to run-head until its key's run is
    found or the bucket is exhausted. -> (matched, pos, run_count, overflow):
    pos is the run head's sorted position, run_count its length."""
    from jax import lax

    strict = sel
    for k in keys:
        if k.valid is not None:
            strict = strict & k.valid
    h = _bucket_hash(keys)
    slot = (h & jnp.uint32(table.size - 1)).astype(jnp.int32)
    start = table.starts[slot]
    end = start + table.counts[slot]
    if table.bounds is not None:
        word, in_b = pack_join_keys(keys, table.bounds)
        # an out-of-bounds probe key cannot equal any (in-bounds) build key
        strict = strict & in_b
        kvals = [word]
    else:
        kvals = [_canon_values(k) for k in keys]
    n = table.rows_sorted.shape[0]
    npos = jnp.int32(n)

    def cond(st):
        return jnp.any(st[1]) & (st[4] < num_probes)

    def body(st):
        pos, active, matched, mpos, i = st
        safe = jnp.clip(pos, 0, n - 1)
        hit = active
        for kv, ks in zip(kvals, table.keys_sorted):
            hit = hit & (kv == ks[safe])
        matched = matched | hit
        mpos = jnp.where(hit, safe, mpos)
        # hop to the next run head in this bucket
        nxt = jnp.where(pos + 1 < npos,
                        table.next_head[jnp.clip(pos + 1, 0, n - 1)], BIG)
        active = active & ~hit & (nxt < end)
        return (jnp.where(active, nxt, pos), active, matched, mpos, i + 1)

    init = (start, strict & (table.counts[slot] > 0),
            jnp.zeros_like(sel), jnp.zeros(sel.shape, jnp.int32), jnp.int32(0))
    _, active, matched, mpos, _ = lax.while_loop(cond, body, init)
    safe = jnp.clip(mpos, 0, n - 1)
    nxt = jnp.where(mpos + 1 < npos,
                    table.next_head[jnp.clip(mpos + 1, 0, n - 1)], BIG)
    run_end = jnp.minimum(jnp.minimum(nxt, end), table.n_live)
    run_count = jnp.where(matched, run_end - safe, 0)
    return matched, safe, run_count, jnp.any(active)


def probe(table: SortTable, keys: list[KeySpec], sel, num_probes: int):
    """-> (matched bool[n], build_row int32[n], walk_overflow bool scalar)
    over the probe batch. Duplicate build keys resolve to the run head =
    smallest build row index (the stable sort preserves row order within a
    run). walk_overflow means the hop bound was hit with probes still
    active — the caller must OR it into its overflow flag so the executor
    retries at the next tier (bigger table, higher bound)."""
    matched, pos, _, ov = _walk(table, keys, sel, num_probes)
    return matched, jnp.where(matched, table.rows_sorted[pos], 0), ov


# ---------------------------------------------------------------------------
# Multi-match join: duplicate build keys via the runs themselves
#
# A probe hit knows its run's start position and length, so the output
# expands via prefix sums over a static output capacity — output row j maps
# to (probe_row[j], build_row[j]), the probe row found in one pass over the
# slots (`expand_slots`); an overflow flag plus the exact total cardinality
# feed the executor's tier retry, standing in for nodeHashjoin's dynamic
# batching under XLA's static shapes.
# ---------------------------------------------------------------------------


def expand_slots(cum, count, out_cap: int):
    """-> (probe_row int32[out_cap], ordinal int32[out_cap]): slot j of the
    expansion belongs to the last probe row whose run starts at or before j,
    and is that run's ordinal-th pair. `cum` is the running sum (int64) of
    `count`. Exact on every slot below cum[-1]; past it probe_row stays
    inside [0, P - 1] and nothing reads either.

    One pass over the slots: the slots' probe rows never decrease, so every
    probe row with a pair writes its number at its run's first slot (those
    slots are distinct; a row without a pair and a run that starts at or
    past `out_cap` write nothing) and a prefix max carries it over the run.
    A run's first slot is where the probe row changes, and a second prefix
    max carries that slot's number over the run for the ordinal. All int32:
    `out_cap` < 2^31. On a TPU v5e the scatter is 4.7-4.9 ns a probe row and
    the two prefix maxes with what is elementwise around them 0.84-0.9 ns a
    slot: 20 ms at 2^24 slots over 2^20 rows, where a `searchsorted` of
    `cum` a slot (21 rounds of gathers of an int64's two limbs, 31-34 ns a
    slot a round) is 10,870 (PERF.md §6, PR 38). A `set` that drops the rows
    without a pair costs half of a `max` over every row's start, and the
    second prefix max a twentieth of a gather of the starts by probe row."""
    from jax import lax

    start = jnp.where(count > 0,
                      jnp.minimum(cum - count.astype(jnp.int64), out_cap),
                      out_cap).astype(jnp.int32)
    marks = jnp.zeros((out_cap,), jnp.int32).at[start].set(
        jnp.arange(count.shape[0], dtype=jnp.int32), mode="drop")
    pr = lax.cummax(marks)
    j = jnp.arange(out_cap, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), pr[1:] != pr[:-1]])
    return pr, j - lax.cummax(jnp.where(first, j, 0))


def build_multi(keys: list[KeySpec], sel, table_size: int, num_probes: int,
                key_bounds: list | None = None) -> SortTable:
    return build(keys, sel, table_size, num_probes, key_bounds)


def probe_multi(table: SortTable, keys: list[KeySpec], sel, num_probes: int,
                out_cap: int, left_outer: bool = False):
    """-> (present[K], probe_row[K], build_row[K], matched[K], expand_ov,
    walk_ov, total) where total is the exact output cardinality — the
    executor uses it to size the retry capacity when expand_ov fires.
    walk_ov must feed the TABLE-side overflow flag (grows M/hop bound at
    the next tier), NOT the expansion flag: the expansion flag's retry
    hint sizes out_cap from `total`, which is an UNDERCOUNT when the walk
    gave up early.

    left_outer: unmatched probe rows still emit one output row with
    matched=False (NULL-extended build side downstream)."""
    matched, pos, run_count, walk_ov = _walk(table, keys, sel, num_probes)
    # the expansion proper: in a device trace everything after the walk
    # reads as `join-expand` inside the plan node's `join` / `semi`
    with jax.named_scope("join-expand"):
        count = run_count
        if left_outer:
            count = jnp.where(sel & ~matched, 1, count)
        cum = jnp.cumsum(count.astype(jnp.int64))
        total = cum[-1] if count.shape[0] else jnp.int64(0)
        overflow = total > out_cap
        pr, ordinal = expand_slots(cum, count, out_cap)
        present = jnp.arange(out_cap, dtype=jnp.int64) < total
        m_at = matched[pr]
        n = table.rows_sorted.shape[0]
        build_row = table.rows_sorted[
            jnp.clip(pos[pr] + ordinal, 0, n - 1)]
        build_row = jnp.where(m_at, build_row, 0)
    return present, pr, build_row, m_at & present, overflow, walk_ov, total


def gather_build_columns(build_cols: dict, build_valids: dict, build_row, matched):
    """Pull build-side columns across to the slots of ``build_row``: the
    probe side's capacity, or, where an inner join expects its matches to
    fit 1/32 of the probe slots, the batch its matched probe rows were
    compacted into first (exec/compile.Compiler._join_compact_k).
    Unmatched rows get valid=False (supports LEFT OUTER null-extension for
    free)."""
    out_cols, out_valids = {}, {}
    for name, arr in build_cols.items():
        out_cols[name] = arr[build_row]
        v = build_valids.get(name)
        gv = v[build_row] if v is not None else jnp.ones_like(matched)
        out_valids[name] = gv & matched
    return out_cols, out_valids


# ---------------------------------------------------------------------------
# Direct-addressed join: dense integer build keys (the TPC-H PK-FK case)
#
# When ANALYZE shows the build key's domain [min, max] is comparable to the
# build row count (surrogate/sequence keys: orderkey, custkey, ...), the
# hash table degenerates to a dense array indexed by (key - min): build is
# ONE scatter, probe is ONE gather — measured on v5e, even the sort build
# costs ~1s at 15M rows while this whole join runs in ~2 passes of memory
# bandwidth. Unique-key builds only (the dup flag reports violations for
# the executor's re-plan).
# ---------------------------------------------------------------------------


@dataclass
class DirectTable:
    slot_row: jnp.ndarray
    used: jnp.ndarray
    overflow: jnp.ndarray
    dup: jnp.ndarray
    size: int
    live: jnp.ndarray | None = None   # bool [n]: the build rows the table took


def build_direct(key: KeySpec, sel, lo: int, domain: int) -> DirectTable:
    """Dense build table over key values in [lo, lo+domain)."""
    v = key.values.astype(jnp.int64) - jnp.int64(lo)
    strict = sel
    if key.valid is not None:
        strict = strict & key.valid
    in_dom = strict & (v >= 0) & (v < domain)
    idx = jnp.where(in_dom, v, domain).astype(jnp.int64)
    n = sel.shape[0]
    row_idx = jnp.arange(n, dtype=jnp.int32)
    slot_row = jnp.full((domain + 1,), -1, jnp.int32).at[idx].max(
        jnp.where(in_dom, row_idx, -1))
    used = slot_row[:domain] >= 0
    # duplicates: two build rows claimed the same slot -> counts > 1
    counts = jnp.zeros((domain + 1,), jnp.int32).at[idx].add(
        jnp.where(in_dom, 1, 0))
    dup = jnp.any(counts[:domain] > 1)
    # out-of-domain LIVE build keys cannot be represented -> overflow
    # (executor retries; the planner widens the domain from fresh stats)
    overflow = jnp.any(strict & ~in_dom)
    return DirectTable(
        slot_row=slot_row[:domain], used=used, overflow=overflow, dup=dup,
        size=domain, live=in_dom)


def probe_direct(table: DirectTable, key: KeySpec, sel, lo: int):
    """-> (matched, build_row) — one gather, no walk, no key re-compare
    (slot index IS the key)."""
    v = key.values.astype(jnp.int64) - jnp.int64(lo)
    strict = sel
    if key.valid is not None:
        strict = strict & key.valid
    in_dom = strict & (v >= 0) & (v < table.size)
    idx = jnp.where(in_dom, v, 0).astype(jnp.int64)
    row = table.slot_row[idx]
    matched = in_dom & (row >= 0)
    return matched, jnp.where(matched, row, 0)
