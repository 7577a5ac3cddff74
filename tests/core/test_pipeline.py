"""Staged pipeline (repro.core.pipeline) — refactor bit-identity, stage
composition, cache-filter semantics, and the validated RequestStream
ingestion point.

The legacy ``modeled_*`` entry points are compared against the
*pre-refactor* compositions, which survive verbatim as the
``*_seq`` compositions in ``channels.py`` (and, for
``modeled_gather_time``, as the inline seed formula).
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cache_engine import (filter_trace_rw,
                                     filter_trace_rw_parallel,
                                     filter_trace_rw_seq)
from repro.core.channels import (AddressMap,
                                 schedule_and_simulate_channels_seq,
                                 simulate_multiport_channels_seq)
from repro.core.config import (CacheConfig, ChannelConfig,
                               MemoryControllerConfig, SchedulerConfig)
from repro.core.controller import MemoryController
from repro.core.pipeline import (CacheFilterStage, PipelineContext,
                                 RequestStream, default_stages,
                                 run_pipeline)
from repro.core.scheduler import schedule_trace
from repro.core.timing import DDR4_2400, HBM_V5E, simulate_dram_access

MAP_POLICIES = ("row_interleave", "block_interleave", "xor")


def _assert_channel_results_equal(a, b):
    assert a.makespan_fpga_cycles == b.makespan_fpga_cycles
    assert a.busy_fpga_cycles == b.busy_fpga_cycles
    assert a.arbitration_cycles == b.arbitration_cycles
    assert a.requests_per_channel == b.requests_per_channel
    assert a.row_hits == b.row_hits
    assert a.row_conflicts == b.row_conflicts
    assert a.first_accesses == b.first_accesses


# ---------------------------------------------------------------------------
# Legacy entry points are bit-identical to their pre-refactor outputs
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 900), st.integers(0, 1)),
                min_size=0, max_size=250),
       st.sampled_from([1, 2, 4]),
       st.sampled_from(MAP_POLICIES),
       st.booleans(),
       st.booleans())
def test_property_modeled_access_time_unchanged(reqs, num_channels,
                                                policy, coalesce, hbm):
    """AddressMap → BatchScheduler → DRAMService subset == the
    pre-refactor per-channel schedule+simulate composition, bit for
    bit (SimResult and full ChannelSimResult)."""
    rows = np.asarray([r[0] for r in reqs], np.int64)
    rw = np.asarray([r[1] for r in reqs], np.int32)
    cfg = MemoryControllerConfig(
        channels=ChannelConfig(num_channels=num_channels, policy=policy),
        scheduler=SchedulerConfig(batch_size=32))
    mc = MemoryController(cfg, timings=HBM_V5E if hbm else DDR4_2400)
    new = mc.modeled_channel_access_time(rows, rw, 4096,
                                         coalesce_writes=coalesce)
    old = schedule_and_simulate_channels_seq(
        rows * 4096, rw, sched_config=cfg.scheduler, timings=mc.timings,
        channel_cfg=cfg.channels, coalesce_writes=coalesce)
    _assert_channel_results_equal(new, old)
    flat = mc.modeled_access_time(rows, rw, 4096, coalesce_writes=coalesce)
    assert flat.total_fpga_cycles == old.makespan_fpga_cycles


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 700),
                          st.integers(0, 1)),
                min_size=0, max_size=200),
       st.sampled_from([1, 4]),
       st.sampled_from(["round_robin", "priority", "weighted"]),
       st.booleans())
def test_property_modeled_multiport_unchanged(reqs, num_channels,
                                              arb_policy, sched_on):
    rows = np.asarray([r[1] for r in reqs], np.int64)
    pe = np.asarray([r[0] for r in reqs], np.int64)
    rw = np.asarray([r[2] for r in reqs], np.int32)
    weights = [2, 1, 3, 1] if arb_policy == "weighted" else None
    cfg = MemoryControllerConfig(
        num_pes=4, channels=ChannelConfig(num_channels=num_channels),
        scheduler=SchedulerConfig(enabled=sched_on, batch_size=16))
    mc = MemoryController(cfg)
    new = mc.modeled_multiport_access_time(pe, rows, rw, 4096,
                                           policy=arb_policy,
                                           weights=weights)
    old = simulate_multiport_channels_seq(
        pe, rows * 4096, rw, num_ports=4, policy=arb_policy,
        weights=weights, timings=mc.timings, channel_cfg=cfg.channels,
        sched_config=cfg.scheduler if sched_on else None)
    _assert_channel_results_equal(new, old)
    np.testing.assert_array_equal(new.port_stats.grants,
                                  old.port_stats.grants)
    np.testing.assert_array_equal(new.port_stats.stall_slots,
                                  old.port_stats.stall_slots)
    assert new.port_stats.fairness == old.port_stats.fairness


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 2000), min_size=0, max_size=300),
       st.booleans())
def test_property_modeled_gather_time_seed_identity(rows, sched_on):
    """At num_channels=1 the pipelined modeled_gather_time reproduces the
    seed ``schedule_trace`` + ``simulate_dram_access`` composition."""
    rows = np.asarray(rows, np.int64)
    cfg = MemoryControllerConfig(
        scheduler=SchedulerConfig(enabled=sched_on))
    mc = MemoryController(cfg)
    new = mc.modeled_gather_time(rows, 512)
    served = schedule_trace(rows * 512, np.zeros(rows.shape[0], np.int32),
                            config=cfg.scheduler, timings=mc.timings)
    old = simulate_dram_access(served, mc.timings)
    assert new.total_fpga_cycles == old.total_fpga_cycles
    assert (new.row_hits, new.row_conflicts, new.first_accesses) == \
        (old.row_hits, old.row_conflicts, old.first_accesses)


def test_modeled_gather_time_respects_channels(rng):
    """Regression (ISSUE 4 satellite): modeled_gather_time used to call
    schedule_trace + simulate_dram_access directly, so a multi-channel
    controller reported single-channel numbers for read-only traces. It
    must now agree with the channel-decomposed read path and beat the
    single-interface makespan on an irregular trace."""
    rows = rng.integers(0, 1 << 14, 20000)
    mc1 = MemoryController(MemoryControllerConfig())
    mc4 = MemoryController(MemoryControllerConfig(
        channels=ChannelConfig(num_channels=4)))
    t1 = mc1.modeled_gather_time(rows, 512)
    t4 = mc4.modeled_gather_time(rows, 512)
    assert t4.total_fpga_cycles < t1.total_fpga_cycles
    via_channels = mc4.modeled_channel_access_time(
        rows, np.zeros(rows.shape[0], np.int32), 512).as_sim_result()
    assert t4.total_fpga_cycles == via_channels.total_fpga_cycles
    assert t4.row_hits == via_channels.row_hits


# ---------------------------------------------------------------------------
# RequestStream — the validated ingestion point
# ---------------------------------------------------------------------------

def test_from_rows_rejects_bad_inputs(rng):
    good = rng.integers(0, 100, 16)
    with pytest.raises(ValueError, match="negative"):
        RequestStream.from_rows(np.asarray([3, -1, 2]), row_bytes=64)
    with pytest.raises(ValueError, match="overflow"):
        RequestStream.from_rows(np.asarray([1 << 60]), row_bytes=1024)
    with pytest.raises(ValueError, match="row_bytes"):
        RequestStream.from_rows(good, row_bytes=0)
    with pytest.raises(TypeError, match="integer"):
        RequestStream.from_rows(good.astype(np.float32), row_bytes=64)
    with pytest.raises(ValueError, match="one entry per request"):
        RequestStream.from_rows(good, np.zeros(5, np.int32), row_bytes=64)
    with pytest.raises(ValueError, match="0 .*read.* or 1"):
        RequestStream.from_rows(good, np.full(16, 2), row_bytes=64)
    with pytest.raises(ValueError, match="pe_id"):
        RequestStream.from_rows(good, pe_id=np.zeros(3), row_bytes=64)
    s = RequestStream.from_rows(good, rng.integers(0, 2, 16),
                                row_bytes=64, pe_id=rng.integers(0, 4, 16))
    assert len(s) == 16
    np.testing.assert_array_equal(s.addr, good.astype(np.int64) * 64)
    np.testing.assert_array_equal(s.seq, np.arange(16))


# ---------------------------------------------------------------------------
# Cache filter — oracle identity, write policies, channel commutation
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3000), st.integers(0, 1)),
                min_size=0, max_size=300),
       st.sampled_from([(1, 256), (2, 512), (4, 1024), (8, 4096)]),
       st.sampled_from(["write_back", "write_through"]))
def test_property_cache_filter_fast_vs_seq(reqs, shape, policy):
    ways, lines = shape
    cfg = CacheConfig(num_lines=lines, associativity=ways,
                      write_policy=policy)
    lids = np.asarray([r[0] for r in reqs], np.int64)
    rw = np.asarray([r[1] for r in reqs], np.int32)
    fast = filter_trace_rw_parallel(cfg, lids, rw)
    ref = filter_trace_rw_seq(cfg, lids, rw)
    np.testing.assert_array_equal(fast.hits, ref.hits)
    np.testing.assert_array_equal(fast.keep, ref.keep)
    np.testing.assert_array_equal(fast.wb_pos, ref.wb_pos)
    np.testing.assert_array_equal(fast.wb_line, ref.wb_line)


def test_cache_filter_write_policies_hand_case():
    """Direct-mapped 1-set view of the policy split: write-back absorbs
    the write hit and flushes the dirty victim on eviction; write-through
    forwards every write and never writes back."""
    cfg_wb = CacheConfig(num_lines=256, associativity=1,
                         write_policy="write_back")
    # conflict chain within one set: lines 0, 256, 512 all map to set 0
    lids = np.asarray([0, 0, 256, 512], np.int64)
    rw = np.asarray([1, 1, 0, 0], np.int32)   # write, write-hit, evict, evict
    r = filter_trace_rw_seq(cfg_wb, lids, rw)
    np.testing.assert_array_equal(r.hits, [False, True, False, False])
    np.testing.assert_array_equal(r.keep, [True, False, True, True])
    np.testing.assert_array_equal(r.wb_pos, [2])   # dirty line 0 flushed
    np.testing.assert_array_equal(r.wb_line, [0])  # ... when 256 evicts it
    cfg_wt = CacheConfig(num_lines=256, associativity=1,
                         write_policy="write_through")
    r = filter_trace_rw_seq(cfg_wt, lids, rw)
    np.testing.assert_array_equal(r.hits, [False, True, False, False])
    np.testing.assert_array_equal(r.keep, [True, True, True, True])
    assert r.n_writebacks == 0


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 500), st.integers(0, 1)),
                min_size=0, max_size=250),
       st.sampled_from([1, 2, 4]),
       st.sampled_from(MAP_POLICIES),
       st.sampled_from(["write_back", "write_through"]))
def test_property_filter_commutes_with_channel_split(reqs, num_channels,
                                                     map_policy, wpolicy):
    """The cache is banked per channel, so filtering the global annotated
    stream (per-channel states, arrival order) == splitting by channel
    and filtering each substream independently."""
    cache = CacheConfig(num_lines=256, associativity=2,
                        write_policy=wpolicy)
    ccfg = ChannelConfig(num_channels=num_channels, policy=map_policy)
    amap = AddressMap(ccfg, DDR4_2400)
    addrs = np.asarray([r[0] * 4096 for r in reqs], np.int64)
    rw = np.asarray([r[1] for r in reqs], np.int32)
    ch = amap.channel_of(addrs)
    lids = amap.local_addr(addrs) // cache.line_bytes

    # filter-then-split: one walk over the global stream with per-channel
    # banked dict states (independent reference implementation)
    sets, ways = cache.num_sets, cache.associativity
    wb = wpolicy == "write_back"
    states: dict = {}
    g_hits = np.zeros(addrs.shape[0], bool)
    g_keep = np.ones(addrs.shape[0], bool)
    g_wb: list[tuple[int, int, int]] = []     # (pos, channel, line)
    for i in range(addrs.shape[0]):
        k, lid = int(ch[i]), int(lids[i])
        s, t = lid % sets, lid // sets
        e = states.setdefault((k, s), {})
        w = int(rw[i]) == 1
        if t in e:
            g_hits[i] = True
            e[t] = [i, wb if w else e[t][1]]
            g_keep[i] = w and not wb
        else:
            if len(e) >= ways:
                vt = min(e, key=lambda x: e[x][0])
                if e[vt][1]:
                    g_wb.append((i, k, vt * sets + s))
                del e[vt]
            e[t] = [i, w and wb]

    # split-then-filter: what the pipeline's CacheFilter stage runs
    for k in range(num_channels):
        sel = np.flatnonzero(ch == k)
        res = filter_trace_rw(cache, lids[sel], rw[sel])
        np.testing.assert_array_equal(res.hits, g_hits[sel])
        np.testing.assert_array_equal(res.keep, g_keep[sel])
        mine = [(p, line) for p, kk, line in g_wb if kk == k]
        # global position → position within the channel substream
        np.testing.assert_array_equal(
            res.wb_pos, [int(np.searchsorted(sel, p)) for p, _ in mine])
        np.testing.assert_array_equal(res.wb_line,
                                      [line for _, line in mine])


def test_cache_filter_stage_stream_is_coherent(rng):
    """Stage-level invariants of the filtered stream: write-backs are
    tagged, every address recomposes onto its annotated channel (the
    AddressMap bijection inverse), and the kept requests are exactly the
    filter's keep set."""
    cfg = MemoryControllerConfig(
        cache=CacheConfig(num_lines=256, associativity=2),
        channels=ChannelConfig(num_channels=4))
    ctx = PipelineContext.from_config(cfg, DDR4_2400)
    rows = rng.integers(0, 2000, 3000)
    rw = rng.integers(0, 2, 3000)
    stream = RequestStream.from_rows(rows, rw, row_bytes=4096)
    stages = default_stages(ctx, cache=True)
    annotated, _ = stages[0].run(stream, ctx)
    filtered, stats = CacheFilterStage().run(annotated, ctx)
    assert stats.info["n_writebacks"] == int(
        filtered.tags["writeback"].sum())
    assert len(filtered) == stats.out_requests
    amap = ctx.address_map()
    np.testing.assert_array_equal(amap.channel_of(filtered.addr),
                                  filtered.channel)
    np.testing.assert_array_equal(amap.local_addr(filtered.addr),
                                  filtered.local_addr)
    # under write-back every hit (read or write) is absorbed, so the
    # forwarded originals are exactly the misses
    n_orig = int((~filtered.tags["writeback"]).sum())
    assert n_orig == len(annotated) - stats.info["n_hits"]
    assert (filtered.rw[filtered.tags["writeback"]] == 1).all()


# ---------------------------------------------------------------------------
# Full-pipeline composition
# ---------------------------------------------------------------------------

def test_pipeline_cache_disabled_matches_legacy_entry_points(rng):
    """simulate() with the cache engine disabled is bit-identical to
    modeled_access_time / modeled_multiport_access_time (the stage
    subset the wrappers run)."""
    rows = rng.integers(0, 4096, 5000)
    rw = rng.integers(0, 2, 5000)
    pe = rng.integers(0, 8, 5000)
    cfg = MemoryControllerConfig(
        cache=CacheConfig(enabled=False),
        channels=ChannelConfig(num_channels=4))
    mc = MemoryController(cfg)
    res = mc.simulate(None, rows, rw, 512)
    _assert_channel_results_equal(
        res.as_channel_result(),
        mc.modeled_channel_access_time(rows, rw, 512))
    assert res.as_sim_result().total_fpga_cycles == \
        mc.modeled_access_time(rows, rw, 512).total_fpga_cycles
    resp = mc.simulate(pe, rows, rw, 512)
    _assert_channel_results_equal(
        resp.as_channel_result(),
        mc.modeled_multiport_access_time(pe, rows, rw, 512))


@pytest.mark.parametrize("n", [0, 1])
def test_pipeline_empty_and_single_request_streams(n, rng):
    """Boundary streams flow through the *full* composition (arbiter +
    cache + scheduler + channels) without special-casing. The
    controller's ``simulate()`` refuses the empty trace (input
    hardening — an all-zero result silently poisons derived numbers),
    so the degenerate run is built from the pipeline primitives."""
    cfg = MemoryControllerConfig(
        channels=ChannelConfig(num_channels=4))
    mc = MemoryController(cfg)
    rows = rng.integers(0, 100, n)
    rw = rng.integers(0, 2, n)
    pe = rng.integers(0, cfg.num_pes, n)
    if n == 0:
        with pytest.raises(ValueError, match="empty trace"):
            mc.simulate(pe, rows, rw, 512)
        ctx = PipelineContext.from_config(cfg, mc.timings)
        stream = RequestStream.from_rows(rows, rw, row_bytes=512,
                                         pe_id=pe)
        res = run_pipeline(stream, ctx,
                           default_stages(ctx, ports=cfg.num_pes))
    else:
        res = mc.simulate(pe, rows, rw, 512)
    assert res.n_requests == n
    assert sum(res.requests_per_channel) == n
    assert len(res.per_channel) == 4
    assert res.makespan_fpga_cycles >= cfg.ctrl_overhead_cycles
    if n == 0:
        assert res.dram_makespan_fpga_cycles == 0.0
        assert res.cache_hit_rate == 0.0
    else:
        assert res.dram_makespan_fpga_cycles > 0.0
    assert res.port_stats is not None
    assert int(res.port_stats.grants.sum()) == n


def test_pipeline_breakdown_sums_to_makespan(rng):
    mc = MemoryController(MemoryControllerConfig(
        channels=ChannelConfig(num_channels=2)))
    rows = rng.integers(0, 1 << 13, 8000)
    rw = rng.integers(0, 2, 8000)
    res = mc.simulate(None, rows, rw, 512)
    bd = res.breakdown()
    assert bd["ctrl_overhead"] == mc.config.ctrl_overhead_cycles
    assert abs(sum(bd.values()) - res.makespan_fpga_cycles) < 1e-6
    assert [s.name for s in res.stages] == [
        "address_map", "cache_filter", "batch_scheduler",
        "dram_service", "dma_overlap"]


def test_combined_cache_channels_beats_scheduler_only(rng):
    """The headline composition: cache + scheduler + channels together
    beat the scheduler-only controller on a cache-friendly irregular
    trace — the paper's claim that the win comes from the composition."""
    rows = (rng.zipf(1.2, 30000) - 1) % (1 << 14)
    rw = rng.integers(0, 2, 30000)
    combined = MemoryController(MemoryControllerConfig(
        channels=ChannelConfig(num_channels=4)))
    sched_only = MemoryController(MemoryControllerConfig(
        cache=CacheConfig(enabled=False),
        channels=ChannelConfig(num_channels=4)))
    a = combined.simulate(None, rows, rw, 512)
    b = sched_only.simulate(None, rows, rw, 512)
    assert a.cache_hit_rate > 0.3
    assert a.makespan_fpga_cycles < b.makespan_fpga_cycles
    # the cache filter genuinely shrank the DRAM stream
    assert a.dram_makespan_fpga_cycles < b.dram_makespan_fpga_cycles


# ---------------------------------------------------------------------------
# RequestStream.select / _concat_streams round-trips (direct coverage —
# previously exercised only through full pipeline runs)
# ---------------------------------------------------------------------------

def _full_stream(rng, n=257):
    s = RequestStream.from_rows(
        rng.integers(0, 5000, n), rng.integers(0, 2, n),
        row_bytes=512, pe_id=rng.integers(0, 8, n))
    amap = AddressMap(ChannelConfig(num_channels=4), DDR4_2400)
    s.channel = amap.channel_of(s.addr)
    s.local_addr = amap.local_addr(s.addr)
    s.tags["writeback"] = rng.random(n) < 0.25
    return s


def _assert_streams_equal(a, b):
    np.testing.assert_array_equal(a.addr, b.addr)
    np.testing.assert_array_equal(a.rw, b.rw)
    np.testing.assert_array_equal(a.pe_id, b.pe_id)
    np.testing.assert_array_equal(a.seq, b.seq)
    np.testing.assert_array_equal(a.channel, b.channel)
    np.testing.assert_array_equal(a.local_addr, b.local_addr)
    assert sorted(a.tags) == sorted(b.tags)
    for k in a.tags:
        np.testing.assert_array_equal(a.tags[k], b.tags[k])


def test_select_permutation_round_trip(rng):
    """select(perm) then select(inverse) restores every array,
    annotations and tags included."""
    s = _full_stream(rng)
    perm = rng.permutation(len(s))
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(s))
    _assert_streams_equal(s.select(perm).select(inv), s)
    # sub-selection keeps the tag rows aligned with the requests
    sel = np.flatnonzero(s.rw == 1)
    sub = s.select(sel)
    assert len(sub) == sel.size
    np.testing.assert_array_equal(sub.tags["writeback"],
                                  s.tags["writeback"][sel])


def test_select_without_annotations_keeps_none(rng):
    s = RequestStream.from_rows(rng.integers(0, 100, 16), row_bytes=64)
    sub = s.select(np.arange(8))
    assert sub.channel is None and sub.local_addr is None


def test_concat_streams_split_round_trip(rng):
    """Splitting a stream into chunks and concatenating restores it —
    the invariant the CacheFilter's per-channel merge relies on."""
    from repro.core.pipeline import _concat_streams
    s = _full_stream(rng)
    cuts = [0, 40, 41, 150, len(s)]
    parts = [s.select(np.arange(a, b)) for a, b in zip(cuts, cuts[1:])]
    _assert_streams_equal(_concat_streams(parts), s)


def test_concat_streams_mixed_annotations_and_tags(rng):
    """A part without annotations poisons the concat to None (a later
    AddressMap run re-annotates); missing tags raise rather than
    silently misalign."""
    from repro.core.pipeline import _concat_streams
    s = _full_stream(rng, n=64)
    bare = RequestStream.from_rows(rng.integers(0, 100, 8),
                                   row_bytes=512)
    bare.tags["writeback"] = np.zeros(8, bool)
    merged = _concat_streams([s, bare])
    assert merged.channel is None and merged.local_addr is None
    assert len(merged) == len(s) + 8
    no_tag = RequestStream.from_rows(rng.integers(0, 100, 8),
                                     row_bytes=512)
    with pytest.raises(KeyError):
        _concat_streams([s, no_tag])


def test_concat_streams_empty_list():
    from repro.core.pipeline import _concat_streams
    out = _concat_streams([])
    assert len(out) == 0 and out.tags == {}


# ---------------------------------------------------------------------------
# PipelineResult legacy-view adapters (direct coverage)
# ---------------------------------------------------------------------------

def test_as_channel_result_and_as_sim_result_fields(rng):
    """The adapters reproduce the DRAM-service + arbitration view:
    makespan = slowest channel + arbiter fill, counts aggregate over
    channels, and the SimResult view collapses the same numbers."""
    cfg = MemoryControllerConfig(
        channels=ChannelConfig(num_channels=4))
    mc = MemoryController(cfg)
    rows = rng.integers(0, 4096, 4000)
    rw = rng.integers(0, 2, 4000)
    pe = rng.integers(0, cfg.num_pes, 4000)
    res = mc.simulate(pe, rows, rw, 512)
    ch = res.as_channel_result()
    assert ch.arbitration_cycles == res.arbitration_cycles
    assert ch.per_channel == res.per_channel
    assert ch.requests_per_channel == res.requests_per_channel
    assert ch.makespan_fpga_cycles == pytest.approx(
        max(r.total_fpga_cycles for r in res.per_channel)
        + res.arbitration_cycles)
    assert ch.busy_fpga_cycles == pytest.approx(
        sum(r.total_fpga_cycles for r in res.per_channel))
    assert ch.port_stats is res.port_stats
    assert ch.row_hits == sum(r.row_hits for r in res.per_channel)
    sim = res.as_sim_result()
    assert sim.total_fpga_cycles == ch.makespan_fpga_cycles
    assert (sim.row_hits, sim.row_conflicts, sim.first_accesses) == \
        (ch.row_hits, ch.row_conflicts, ch.first_accesses)
    assert sim.hit_rate == pytest.approx(ch.hit_rate)


def test_adapters_on_empty_pipeline():
    """simulate() hard-fails on an empty trace; the legacy result
    adapters still handle the degenerate pipeline run cleanly."""
    cfg = MemoryControllerConfig(channels=ChannelConfig(num_channels=2))
    mc = MemoryController(cfg)
    with pytest.raises(ValueError, match="empty trace"):
        mc.simulate(None, np.empty(0, np.int64), None, 512)
    ctx = PipelineContext.from_config(cfg, mc.timings)
    stream = RequestStream.from_rows(np.empty(0, np.int64), None,
                                     row_bytes=512)
    res = run_pipeline(stream, ctx, default_stages(ctx))
    ch = res.as_channel_result()
    assert ch.makespan_fpga_cycles == 0.0
    assert ch.requests_per_channel == [0, 0]
    sim = res.as_sim_result()
    assert (sim.total_fpga_cycles, sim.row_hits) == (0.0, 0)
