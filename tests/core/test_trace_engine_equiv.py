"""Set-parallel / vectorized fast paths vs their sequential oracles.

Every hot loop that was vectorized in the trace engine PR keeps its
original request-at-a-time implementation as a ``*_seq`` sibling; these
property tests assert the fast paths are *value- and state-identical*
(bit-for-bit, not approximately) across random configurations — sets,
ways, write policies, timeouts, mixed read/write streams, skewed traces
and chained (dirty) cache states.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.cache_engine import (flush, hit_rate_oracle,
                                     hit_rate_oracle_seq, init_cache,
                                     simulate_trace, simulate_trace_seq,
                                     simulate_trace_rw,
                                     simulate_trace_rw_seq)
from repro.core.config import CacheConfig, SchedulerConfig
from repro.core.scheduler import (form_batches, form_batches_seq,
                                  form_batches_typed,
                                  form_batches_typed_seq,
                                  schedule_trace_rw, schedule_trace_rw_seq)
from repro.core.timing import (DDR4_2400, simulate_dram_access_windowed,
                               simulate_dram_access_windowed_seq)
from repro.core.trace_engine import (simulate_trace_parallel,
                                     simulate_trace_rw_parallel)


def _assert_state_equal(a, b):
    for field in ("tags", "valid", "age", "data", "clock", "dirty"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)),
                                      err_msg=field)


# ---------------------------------------------------------------------------
# Cache engine: set-parallel vs sequential scan
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 900), min_size=1, max_size=250),
       st.sampled_from([1, 2, 8]),
       st.booleans())
def test_property_read_trace_set_parallel_identical(lids, ways, warm):
    cfg = CacheConfig(num_lines=256, associativity=ways)
    rng = np.random.default_rng(len(lids) + ways)
    table = jnp.asarray(rng.standard_normal((1024, 3)), jnp.float32)
    state = init_cache(cfg, 3)
    if warm:    # chained state, same lineage (clean reads keep coherence)
        state, _, _ = simulate_trace_seq(
            state, jnp.asarray(rng.integers(0, 1024, 64), jnp.int32), table)
    lids = jnp.asarray(lids, jnp.int32)
    f_seq, h_seq, l_seq = simulate_trace_seq(state, lids, table)
    f_par, h_par, l_par = simulate_trace_parallel(state, lids, table)
    _assert_state_equal(f_seq, f_par)
    np.testing.assert_array_equal(np.asarray(h_seq), np.asarray(h_par))
    np.testing.assert_array_equal(np.asarray(l_seq), np.asarray(l_par))


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 600), st.integers(0, 1)),
                min_size=1, max_size=200),
       st.sampled_from(["write_back", "write_through"]),
       st.sampled_from([1, 4]),
       st.booleans())
def test_property_rw_trace_set_parallel_identical(reqs, policy, ways, warm):
    """Mixed read/write stream: final state, backing table (raw and
    flushed), hit flags and served lines all match the one-beat-at-a-time
    scan — including when starting from a chained dirty state."""
    cfg = CacheConfig(num_lines=256, associativity=ways,
                      write_policy=policy)
    rng = np.random.default_rng(len(reqs) * 2 + ways)
    table = jnp.asarray(rng.standard_normal((640, 2)), jnp.float32)
    state = init_cache(cfg, 2)
    if warm:    # enter with dirty lines from a prior trace (same lineage)
        n0 = 48
        state, table, _, _ = simulate_trace_rw_seq(
            state, jnp.asarray(rng.integers(0, 640, n0), jnp.int32),
            jnp.asarray(rng.integers(0, 2, n0), jnp.int32),
            jnp.asarray(rng.standard_normal((n0, 2)), jnp.float32),
            table, config=cfg)
    n = len(reqs)
    lids = jnp.asarray([r[0] for r in reqs], jnp.int32)
    rw = jnp.asarray([r[1] for r in reqs], jnp.int32)
    wlines = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    seq = simulate_trace_rw_seq(state, lids, rw, wlines, table, config=cfg)
    par = simulate_trace_rw_parallel(
        state, lids, rw, wlines, table,
        write_back=cfg.write_policy == "write_back")
    _assert_state_equal(seq[0], par[0])
    np.testing.assert_array_equal(np.asarray(seq[1]), np.asarray(par[1]))
    np.testing.assert_array_equal(np.asarray(seq[2]), np.asarray(par[2]))
    np.testing.assert_array_equal(np.asarray(seq[3]), np.asarray(par[3]))
    _, t_seq = flush(seq[0], seq[1])
    _, t_par = flush(par[0], par[1])
    np.testing.assert_array_equal(np.asarray(t_seq), np.asarray(t_par))


def test_auto_dispatch_falls_back_and_stays_identical(rng):
    """The input-chosen engine must be safe everywhere: tiny traces, out-of-table
    ids and dirty read-states take the sequential path transparently."""
    cfg = CacheConfig(num_lines=256, associativity=2)
    table = jnp.asarray(rng.standard_normal((64, 2)), jnp.float32)
    state = init_cache(cfg, 2)
    lids = jnp.asarray(rng.integers(0, 500, 40), jnp.int32)  # ids > rows
    rw = jnp.asarray(rng.integers(0, 2, 40), jnp.int32)
    wl = jnp.asarray(rng.standard_normal((40, 2)), jnp.float32)
    auto = simulate_trace_rw(state, lids, rw, wl, table, config=cfg)
    seq = simulate_trace_rw_seq(state, lids, rw, wl, table, config=cfg)
    _assert_state_equal(auto[0], seq[0])
    np.testing.assert_array_equal(np.asarray(auto[1]), np.asarray(seq[1]))


def test_auto_dispatch_incoherent_state_falls_back(rng):
    """A state warmed against a *different* table violates the
    clean-line coherence precondition; the engine choice must detect it and
    serve the seed semantics (hits serve the Data RAM copy, not the
    passed table)."""
    cfg = CacheConfig(num_lines=256, associativity=2)
    table_a = jnp.asarray(rng.standard_normal((512, 2)), jnp.float32)
    table_b = jnp.asarray(rng.standard_normal((512, 2)), jnp.float32)
    state = init_cache(cfg, 2)
    warm = jnp.asarray(rng.integers(0, 512, 300), jnp.int32)
    state, _, _ = simulate_trace_seq(state, warm, table_a)
    lids = jnp.asarray(rng.integers(0, 512, 400), jnp.int32)
    f_auto, h_auto, l_auto = simulate_trace(state, lids, table_b)
    f_seq, h_seq, l_seq = simulate_trace_seq(state, lids, table_b)
    _assert_state_equal(f_auto, f_seq)
    np.testing.assert_array_equal(np.asarray(h_auto), np.asarray(h_seq))
    np.testing.assert_array_equal(np.asarray(l_auto), np.asarray(l_seq))


def test_auto_dispatch_out_of_table_dirty_line_falls_back(rng):
    """A resident dirty way caching a line beyond the (smaller) passed
    table would flush out of bounds; auto must fall back to the clipping
    sequential semantics instead of crashing or diverging."""
    cfg = CacheConfig(num_lines=256, associativity=1,
                      write_policy="write_back")
    big = jnp.asarray(rng.standard_normal((2048, 2)), jnp.float32)
    state = init_cache(cfg, 2)
    n0 = 64
    state, big, _, _ = simulate_trace_rw_seq(
        state, jnp.asarray(rng.integers(1500, 2048, n0), jnp.int32),
        jnp.ones(n0, jnp.int32),
        jnp.asarray(rng.standard_normal((n0, 2)), jnp.float32),
        big, config=cfg)
    small = jnp.asarray(rng.standard_normal((640, 2)), jnp.float32)
    n = 400
    lids = jnp.asarray(rng.integers(0, 640, n), jnp.int32)
    rw = jnp.asarray(rng.integers(0, 2, n), jnp.int32)
    wl = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    auto = simulate_trace_rw(state, lids, rw, wl, small, config=cfg)
    seq = simulate_trace_rw_seq(state, lids, rw, wl, small, config=cfg)
    _assert_state_equal(auto[0], seq[0])
    np.testing.assert_array_equal(np.asarray(auto[1]), np.asarray(seq[1]))


def test_schedule_trace_rw_negative_addresses_identical():
    """Negative addresses produce negative row indices; the fused-key
    sort must not be used there (batch key ranges would overlap)."""
    addrs = np.array([-8192, 8192, -16384, 0, 8192, -8192, 0, -16384])
    rw = np.zeros(8, np.int32)
    cfg = SchedulerConfig(batch_size=4, bypass_sequential=False)
    fast = schedule_trace_rw(addrs, rw, config=cfg)
    ref = schedule_trace_rw_seq(addrs, rw, config=cfg)
    np.testing.assert_array_equal(fast[0], ref[0])
    np.testing.assert_array_equal(fast[1], ref[1])


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 4000), min_size=0, max_size=400),
       st.sampled_from([(256, 1), (256, 4), (1024, 8)]))
def test_property_hit_rate_oracle_identical(lids, shape):
    num_lines, ways = shape
    cfg = CacheConfig(num_lines=num_lines, associativity=ways)
    lids = np.asarray(lids, np.int64)
    h_seq, r_seq = hit_rate_oracle_seq(cfg, lids)
    h_vec, r_vec = hit_rate_oracle(cfg, lids)
    np.testing.assert_array_equal(h_seq, h_vec)
    assert r_seq == r_vec


# ---------------------------------------------------------------------------
# Scheduler: vectorized batch planning vs request-at-a-time walk
# ---------------------------------------------------------------------------

def _assert_batches_equal(fast, ref):
    fast, ref = list(fast), list(ref)
    assert len(fast) == len(ref)
    for bf, br in zip(fast, ref):
        assert bf.rw == br.rw
        for field in ("pe_id", "addr", "size", "seq"):
            np.testing.assert_array_equal(getattr(bf, field),
                                          getattr(br, field),
                                          err_msg=field)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 30), st.integers(0, 1),
                          st.integers(0, 9)),
                min_size=0, max_size=300),
       st.sampled_from([4, 16, 64]),
       st.sampled_from([4, 10, 40]))
def test_property_batch_formers_identical(reqs, batch_size, timeout):
    addrs = np.array([r[0] * 4096 for r in reqs], np.int64)
    rw = np.array([r[1] for r in reqs], np.int32)
    arrival = np.cumsum([r[2] for r in reqs]).astype(np.int64) \
        if reqs else None
    cfg = SchedulerConfig(batch_size=batch_size, timeout_cycles=timeout)
    for arr in (None, arrival):
        _assert_batches_equal(
            form_batches(addrs, rw, arr, config=cfg),
            form_batches_seq(addrs, rw, arr, config=cfg))
        _assert_batches_equal(
            form_batches_typed(addrs, rw, arr, config=cfg),
            form_batches_typed_seq(addrs, rw, arr, config=cfg))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 40), st.integers(0, 1)),
                min_size=0, max_size=300),
       st.sampled_from([4, 64]),
       st.booleans(), st.booleans())
def test_property_schedule_trace_rw_identical(reqs, batch_size, bypass,
                                              coalesce):
    addrs = np.array([r[0] * 8192 for r in reqs], np.int64)
    rw = np.array([r[1] for r in reqs], np.int32)
    cfg = SchedulerConfig(batch_size=batch_size,
                          bypass_sequential=bypass)
    fast = schedule_trace_rw(addrs, rw, config=cfg,
                             coalesce_writes=coalesce)
    ref = schedule_trace_rw_seq(addrs, rw, config=cfg,
                                coalesce_writes=coalesce)
    np.testing.assert_array_equal(fast[0], ref[0])
    np.testing.assert_array_equal(fast[1], ref[1])


# ---------------------------------------------------------------------------
# Commercial-IP baseline: chunked drain vs per-request greedy walk
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 500), min_size=0, max_size=600),
       st.sampled_from([1, 2, 4, 7]))
def test_property_windowed_baseline_identical(rows, window):
    addrs = np.asarray(rows, np.int64) * 8192 // 4   # mix rows and banks
    fast = simulate_dram_access_windowed(addrs, DDR4_2400, window=window)
    ref = simulate_dram_access_windowed_seq(addrs, DDR4_2400,
                                            window=window)
    assert dataclasses.asdict(fast) == dataclasses.asdict(ref)


def test_windowed_negative_addresses_identical():
    """Negative addresses yield negative row indices — legal values that
    must not collide with any 'bank closed' sentinel."""
    addrs = np.array([-8192, -8192, -8192, 8192, -16384, -8192])
    for window in (1, 2, 4):
        fast = simulate_dram_access_windowed(addrs, window=window)
        ref = simulate_dram_access_windowed_seq(addrs, window=window)
        assert dataclasses.asdict(fast) == dataclasses.asdict(ref)


def test_simulate_trace_auto_negative_ids_identical(rng):
    """Negative line ids wrap python-style through the sequential jnp
    gather; auto must keep them on the sequential path."""
    cfg = CacheConfig(num_lines=256, associativity=2)
    table = jnp.asarray(rng.standard_normal((512, 2)), jnp.float32)
    state = init_cache(cfg, 2)
    lids_np = rng.integers(0, 512, 300)
    lids_np[5] = -3
    lids = jnp.asarray(lids_np, jnp.int32)
    f_auto, h_auto, l_auto = simulate_trace(state, lids, table)
    f_seq, h_seq, l_seq = simulate_trace_seq(state, lids, table)
    _assert_state_equal(f_auto, f_seq)
    np.testing.assert_array_equal(np.asarray(h_auto), np.asarray(h_seq))
    np.testing.assert_array_equal(np.asarray(l_auto), np.asarray(l_seq))


def test_simulate_trace_auto_is_jittable(rng):
    """The seed read path ran inside jit; the engine choice must keep that
    working (traced table ⇒ sequential scan, no host round-trip)."""
    import jax

    cfg = CacheConfig(num_lines=256, associativity=2)
    table = jnp.asarray(rng.standard_normal((512, 2)), jnp.float32)
    state = init_cache(cfg, 2)
    lids = jnp.asarray(rng.integers(0, 512, 300), jnp.int32)

    @jax.jit
    def run(tbl):
        return simulate_trace(state, lids, tbl)

    _, hits, lines = run(table)
    _, h_ref, l_ref = simulate_trace_seq(state, lids, table)
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(lines), np.asarray(l_ref))


def test_windowed_interleaved_streams_identical():
    """The fig7 baseline shape: several sequential bursts round-robin
    interleaved — exercises long hit-run draining."""
    rng = np.random.default_rng(0)
    streams = [b + np.arange(400) * 64 for b in
               rng.integers(0, 1 << 22, 8)]
    addrs = np.stack(streams, axis=1).reshape(-1)
    for window in (1, 4):
        fast = simulate_dram_access_windowed(addrs, window=window)
        ref = simulate_dram_access_windowed_seq(addrs, window=window)
        assert dataclasses.asdict(fast) == dataclasses.asdict(ref)


# ---------------------------------------------------------------------------
# Edge cases around the vectorized engines' mode boundaries (ISSUE 9):
# single-request traces, all-miss streams (no reuse anywhere), and
# lengths straddling the compaction threshold / tail-staircase chunks.
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.integers(0, 4000), st.sampled_from([1, 2, 8]),
       st.sampled_from([256, 4096]))
def test_property_single_request_trace_identical(lid, ways, lines):
    """One-request traces exercise every engine's n==1 corner: the
    compacted oracle, the set-parallel scan, and the seq walk must all
    agree — one miss, zero hits."""
    cfg = CacheConfig(num_lines=lines, associativity=ways)
    ids = np.asarray([lid], np.int64)
    h_vec, r_vec = hit_rate_oracle(cfg, ids)
    h_seq, r_seq = hit_rate_oracle_seq(cfg, ids)
    np.testing.assert_array_equal(h_vec, h_seq)
    assert r_vec == r_seq == 0.0
    table = jnp.asarray(np.zeros((4096, 2)), jnp.float32)
    state = init_cache(cfg, 2)
    f_seq, h_seq, l_seq = simulate_trace_seq(
        state, jnp.asarray(ids, jnp.int32), table)
    f_par, h_par, l_par = simulate_trace_parallel(
        state, jnp.asarray(ids, jnp.int32), table)
    _assert_state_equal(f_seq, f_par)
    np.testing.assert_array_equal(np.asarray(h_seq), np.asarray(h_par))
    assert not bool(np.asarray(h_par)[0])


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([64, 255, 256, 257, 1024, 4095, 4096, 4097]),
       st.sampled_from([(256, 4), (1024, 1)]))
def test_property_all_miss_trace_identical(n, shape):
    """Distinct line ids everywhere — zero reuse, the worst case for
    both the tail staircase (every lane survives to the finisher) and
    the compacted layout (every set is cold). Lengths straddle the
    TAIL_CHUNKS steps and the MIN_LOCKSTEP_TRACE=4096 compaction
    threshold. Hit rate must be exactly 0 and both engines identical."""
    lines, ways = shape
    cfg = CacheConfig(num_lines=lines, associativity=ways)
    ids = np.arange(n, dtype=np.int64)
    h_vec, r_vec = hit_rate_oracle(cfg, ids)
    h_seq, r_seq = hit_rate_oracle_seq(cfg, ids)
    np.testing.assert_array_equal(h_vec, h_seq)
    assert r_vec == r_seq == 0.0
    assert not h_vec.any()


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([4090, 4096, 4104, 8192]),
       st.integers(0, 3))
def test_property_compaction_threshold_boundary(n, salt):
    """Reuse-heavy traces at the MIN_LOCKSTEP_TRACE boundary: the
    compacted-lane layout kicks in exactly at n==4096, and the verdict
    must not depend on which side of the threshold the dispatch
    lands."""
    cfg = CacheConfig(num_lines=1024, associativity=4)
    rng = np.random.default_rng(n + salt * 7919)
    ids = (rng.zipf(1.3, n).astype(np.int64) - 1) % 2048
    h_vec, r_vec = hit_rate_oracle(cfg, ids)
    h_seq, r_seq = hit_rate_oracle_seq(cfg, ids)
    np.testing.assert_array_equal(h_vec, h_seq)
    assert r_vec == r_seq
