"""Out-of-order DRAM command scheduling (FR-FCFS + refresh) — the
property harness that locks the new order-dependent service model down.

This is the first model in the repo where the makespan depends on the
service *order*, not just the stream contents, so every property here is
stated against the request-at-a-time oracle
(:func:`repro.core.timing.simulate_dram_sched_seq`) or against the
pre-PR simulators the scheduler must degenerate to:

* vectorized path == oracle, bit for bit, over policy x window x cap x
  refresh x rw x timings;
* window=1 (and policy=fifo at any window) == the per-bank FIFO
  classification of ``simulate_dram_access`` — today's model;
* frfcfs without cap/refresh on read-only traces == the pre-PR windowed
  baseline ``simulate_dram_access_windowed(_seq)`` (same greedy
  oldest-ready-first walk);
* FR-FCFS never loses to FIFO on read-only traces (row-hit superset),
  and never pays more open-row class cycles on mixed rw traces (the
  *turnaround* term can go either way — reordering can split a
  same-direction run, which is why the dominance property is stated on
  the class cycles, see docs/ARCHITECTURE.md §8);
* the starvation cap bounds per-request slip: no request is passed by
  more than ``starvation_cap`` younger requests.
"""

import dataclasses

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core import channels as channels_mod
from repro.core.config import (ChannelConfig, DRAMSchedConfig,
                               MemoryControllerConfig, SchedulerConfig,
                               CacheConfig)
from repro.core.controller import MemoryController
from repro.core.timing import (DDR4_2400, HBM_V5E, simulate_dram_access,
                               simulate_dram_access_windowed,
                               simulate_dram_access_windowed_seq,
                               simulate_dram_sched,
                               simulate_dram_sched_seq)

ROW = DDR4_2400.row_bytes


def _trace(reqs, row_scale=ROW // 2):
    addrs = np.asarray([r[0] for r in reqs], np.int64) * row_scale
    rw = np.asarray([r[1] for r in reqs], np.int32)
    return addrs, rw


def _assert_sched_equal(a, b):
    assert a.total_fpga_cycles == b.total_fpga_cycles
    assert a.row_hits == b.row_hits
    assert a.row_conflicts == b.row_conflicts
    assert a.first_accesses == b.first_accesses
    assert a.n_refreshes == b.n_refreshes
    assert a.refresh_dram_cycles == b.refresh_dram_cycles
    assert a.turnaround_dram_cycles == b.turnaround_dram_cycles
    np.testing.assert_array_equal(a.service_order, b.service_order)


def _slips(service_order: np.ndarray) -> np.ndarray:
    """slip[i] = number of younger requests issued before request i."""
    order = np.asarray(service_order, np.int64)
    n = order.shape[0]
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)
    # O(n^2) reference (test-sized traces): count j > i with pos[j] < pos[i]
    younger = np.arange(n)[None, :] > np.arange(n)[:, None]
    earlier = pos[None, :] < pos[:, None]
    return (younger & earlier).sum(axis=1)


def _class_dram_cycles(res, timings) -> int:
    """Open-row class cycles only — no burst/turnaround/refresh terms."""
    return (res.first_accesses * (timings.t_rcd + timings.t_cl)
            + res.row_hits * timings.t_cl
            + res.row_conflicts * (timings.t_rp + timings.t_rcd
                                   + timings.t_cl))


# ---------------------------------------------------------------------------
# Vectorized path == request-at-a-time oracle (the co-headline identity)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 60), st.integers(0, 1)),
                min_size=0, max_size=220),
       st.sampled_from(["fifo", "frfcfs", "frfcfs_cap"]),
       st.sampled_from([1, 2, 3, 4, 8, 16, 64]),
       st.sampled_from([1, 2, 3, 8, 100]),
       st.sampled_from([(0, 0), (0, 37), (5, 37), (30, 100), (30, 500)]),
       st.booleans(),
       st.booleans())
def test_property_fast_path_matches_oracle(reqs, policy, window, cap,
                                           refresh, use_rw, hbm):
    t_rfc, t_refi = refresh
    timings = HBM_V5E if hbm else DDR4_2400
    addrs, rw = _trace(reqs, row_scale=timings.row_bytes // 2)
    sched = DRAMSchedConfig(policy=policy, reorder_window=window,
                            starvation_cap=cap, t_rfc=t_rfc,
                            t_refi=t_refi)
    a = simulate_dram_sched_seq(addrs, timings, sched,
                                rw if use_rw else None)
    b = simulate_dram_sched(addrs, timings, sched,
                            rw if use_rw else None)
    _assert_sched_equal(a, b)
    # the order is a true permutation of the trace
    assert np.array_equal(np.sort(a.service_order), np.arange(len(reqs)))


# ---------------------------------------------------------------------------
# Degeneracies: window=1 / fifo == today's FIFO classification
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 80), st.integers(0, 1)),
                min_size=0, max_size=250),
       st.sampled_from(["fifo", "frfcfs", "frfcfs_cap"]),
       st.booleans())
def test_property_window1_is_fifo_classification(reqs, policy, hbm):
    """Any policy at window=1 (and fifo at any window) is bit-identical
    to the pre-PR ``simulate_dram_access`` per-bank FIFO model,
    turnarounds included."""
    timings = HBM_V5E if hbm else DDR4_2400
    addrs, rw = _trace(reqs, row_scale=timings.row_bytes // 2)
    legacy = simulate_dram_access(addrs, timings, rw=rw)
    for sched in (DRAMSchedConfig(policy=policy, reorder_window=1),
                  DRAMSchedConfig(policy="fifo", reorder_window=64)):
        for simulate in (simulate_dram_sched, simulate_dram_sched_seq):
            got = simulate(addrs, timings, sched, rw)
            assert got.total_fpga_cycles == legacy.total_fpga_cycles
            assert (got.row_hits, got.row_conflicts,
                    got.first_accesses) == (legacy.row_hits,
                                            legacy.row_conflicts,
                                            legacy.first_accesses)
            np.testing.assert_array_equal(got.service_order,
                                          np.arange(len(reqs)))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 70), min_size=0, max_size=250),
       st.sampled_from([1, 2, 4, 8, 16]))
def test_property_frfcfs_matches_windowed_baseline(rows, window):
    """Pure FR-FCFS (no cap, no refresh) on a read-only trace runs the
    same greedy oldest-ready-first walk as the pre-PR commercial-IP
    baseline ``simulate_dram_access_windowed`` — counts and total must
    be bit-identical (the windowed baseline does not expose order)."""
    addrs = np.asarray(rows, np.int64) * (ROW // 2)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=window)
    new = simulate_dram_sched(addrs, DDR4_2400, sched)
    for old in (simulate_dram_access_windowed(addrs, DDR4_2400,
                                              window=window),
                simulate_dram_access_windowed_seq(addrs, DDR4_2400,
                                                  window=window)):
        assert new.total_fpga_cycles == old.total_fpga_cycles
        assert (new.row_hits, new.row_conflicts, new.first_accesses) == \
            (old.row_hits, old.row_conflicts, old.first_accesses)


# ---------------------------------------------------------------------------
# Dominance: FR-FCFS never loses to FIFO
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 50), min_size=0, max_size=250),
       st.sampled_from(["frfcfs", "frfcfs_cap"]),
       st.sampled_from([2, 4, 8, 32, 128]),
       st.sampled_from([1, 4, 16]))
def test_property_frfcfs_makespan_le_fifo_read_only(rows, policy, window,
                                                    cap):
    """On read-only traces (no refresh) the reorder can only *convert*
    conflicts into row hits — FIFO's hits are a subset of FR-FCFS's
    (misses are issued oldest-first in both, so every same-bank
    adjacent same-row pair survives) — hence makespan <= FIFO."""
    addrs = np.asarray(rows, np.int64) * (ROW // 2)
    fr = simulate_dram_sched(addrs, DDR4_2400,
                             DRAMSchedConfig(policy=policy,
                                             reorder_window=window,
                                             starvation_cap=cap))
    fifo = simulate_dram_sched(addrs, DDR4_2400, DRAMSchedConfig())
    assert fr.total_fpga_cycles <= fifo.total_fpga_cycles
    assert fr.row_hits >= fifo.row_hits


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 50), st.integers(0, 1)),
                min_size=0, max_size=250),
       st.sampled_from([2, 8, 32]))
def test_property_frfcfs_class_cycles_le_fifo_mixed_rw(reqs, window):
    """On mixed read/write traces the *open-row class* cycles still
    dominate FIFO's; the bus-turnaround term alone can regress (hit
    promotion may split a same-direction run — ARCHITECTURE §8), which
    is why the guarantee is stated on the class cycles."""
    addrs, rw = _trace(reqs)
    fr = simulate_dram_sched(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy="frfcfs", reorder_window=window), rw)
    fifo = simulate_dram_sched(addrs, DDR4_2400, DRAMSchedConfig(), rw)
    assert _class_dram_cycles(fr, DDR4_2400) <= \
        _class_dram_cycles(fifo, DDR4_2400)


def test_turnaround_can_regress_under_reorder():
    """The documented counterexample (ARCHITECTURE §8): promoting a
    read hit between two writes adds a W->R->W double turnaround that
    FIFO's W,W,R order does not pay. Pinning it keeps the class-cycles
    statement of the dominance property honest."""
    t = DDR4_2400
    # open bank 0 row 0 with a write, then [W miss(bank1), W miss(bank1),
    # R hit(bank0)]: FIFO issues W,W,W,R (one tWTR); window 2 promotes
    # the read between the two bank-1 writes — issued W,W,R,W pays
    # tWTR + tRTW
    addrs = np.asarray([0, 1 * ROW, 17 * ROW, 0], np.int64)
    rw = np.asarray([1, 1, 1, 0], np.int32)
    fr = simulate_dram_sched(
        addrs, t, DRAMSchedConfig(policy="frfcfs", reorder_window=2), rw)
    fifo = simulate_dram_sched(addrs, t, DRAMSchedConfig(), rw)
    assert fr.turnaround_dram_cycles > fifo.turnaround_dram_cycles
    # ... yet the class cycles never regress
    assert _class_dram_cycles(fr, t) <= _class_dram_cycles(fifo, t)


# ---------------------------------------------------------------------------
# Starvation cap bounds per-request slip
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 40), st.integers(0, 1)),
                min_size=0, max_size=200),
       st.sampled_from([1, 2, 3, 8]),
       st.sampled_from([4, 16, 64]),
       st.booleans())
def test_property_starvation_cap_bounds_slip(reqs, cap, window,
                                             with_refresh):
    """With policy=frfcfs_cap no request is ever passed by more than
    ``starvation_cap`` younger requests, for any window and with
    refresh on or off; plain frfcfs has no such bound (witnessed
    below)."""
    addrs, rw = _trace(reqs)
    sched = DRAMSchedConfig(policy="frfcfs_cap", reorder_window=window,
                            starvation_cap=cap,
                            t_rfc=30 if with_refresh else 0,
                            t_refi=100 if with_refresh else 0)
    res = simulate_dram_sched(addrs, DDR4_2400, sched, rw)
    if len(reqs):
        assert int(_slips(res.service_order).max()) <= cap


def test_uncapped_frfcfs_can_starve_but_cap_binds():
    """A hot-row stream behind one cold miss: plain FR-FCFS slips the
    cold request past every hit; the cap cuts that slip to the
    configured bound."""
    # request 0: bank 1 (cold miss); requests 1..40: bank 0, same row —
    # all hits once open — window covers the whole stream
    addrs = np.asarray([17 * ROW] + [0] * 40, np.int64)
    # open bank 0's row first so the hot run hits from the start
    addrs = np.concatenate([[0], addrs])
    free = simulate_dram_sched(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy="frfcfs", reorder_window=64))
    slip_free = int(_slips(free.service_order)[1])
    assert slip_free == 40          # passed by the entire hot run
    capped = simulate_dram_sched(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy="frfcfs_cap", reorder_window=64,
                        starvation_cap=5))
    assert int(_slips(capped.service_order).max()) <= 5


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 60), st.integers(0, 1)),
                min_size=0, max_size=200),
       st.sampled_from([2, 8, 32]))
def test_property_huge_cap_equals_uncapped(reqs, window):
    addrs, rw = _trace(reqs)
    capped = simulate_dram_sched(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy="frfcfs_cap", reorder_window=window,
                        starvation_cap=1 << 20), rw)
    free = simulate_dram_sched(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy="frfcfs", reorder_window=window), rw)
    _assert_sched_equal(capped, free)


# ---------------------------------------------------------------------------
# Refresh accounting
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 60), st.integers(0, 1)),
                min_size=1, max_size=200),
       st.sampled_from(["fifo", "frfcfs"]),
       st.sampled_from([(5, 37), (30, 100), (100, 500)]))
# A refresh that helps: it closes row 0 of bank 0, so the access to row
# 16 is a first access (t_rcd + t_cl) instead of a conflict (t_rp +
# t_rcd + t_cl), saving t_rp = 17 clocks for a refresh of 5 (20.244
# FPGA cycles with refresh against 23.243 without).
@example([(0, 0), (32, 0)], "fifo", (5, 37))
def test_property_refresh_charged_and_never_helps(reqs, policy, refresh):
    """Refresh is charged exactly and never gains a hit; its precharge
    of every bank can help only by turning a conflict into a first
    access, which saves that conflict's ``t_rp`` and no more."""
    t_rfc, t_refi = refresh
    addrs, rw = _trace(reqs)
    base = simulate_dram_sched(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy=policy, reorder_window=8), rw)
    ref = simulate_dram_sched(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy=policy, reorder_window=8,
                        t_rfc=t_rfc, t_refi=t_refi), rw)
    assert ref.refresh_dram_cycles == ref.n_refreshes * t_rfc
    assert ref.row_hits <= base.row_hits
    saved = max(0, base.row_conflicts - ref.row_conflicts)
    assert ref.total_fpga_cycles >= base.total_fpga_cycles \
        - saved * DDR4_2400.t_rp * DDR4_2400.clock_ratio - 1e-9
    if reqs == [(0, 0), (32, 0)] and policy == "fifo" \
            and refresh == (5, 37):
        assert (round(base.total_fpga_cycles, 3),
                round(ref.total_fpga_cycles, 3)) == (23.243, 20.244)


def test_refresh_closes_rows_hand_case():
    """Two same-row accesses with a refresh boundary between them: the
    second re-activates (charged like a first access) instead of
    hitting."""
    t = DDR4_2400
    addrs = np.asarray([0, 0], np.int64)
    no_ref = simulate_dram_sched(addrs, t, DRAMSchedConfig())
    assert (no_ref.first_accesses, no_ref.row_hits) == (1, 1)
    # first access costs t_rcd+t_cl+t_burst = 38 > t_refi=10: refresh
    # fires before the second issue and precharges bank 0
    ref = simulate_dram_sched(
        addrs, t, DRAMSchedConfig(t_rfc=7, t_refi=10))
    assert ref.n_refreshes >= 1
    assert (ref.first_accesses, ref.row_hits) == (2, 0)


# ---------------------------------------------------------------------------
# Config validation + footprint
# ---------------------------------------------------------------------------

def test_dram_sched_config_validation():
    with pytest.raises(ValueError, match="policy"):
        DRAMSchedConfig(policy="open_page")
    with pytest.raises(ValueError, match="reorder_window"):
        DRAMSchedConfig(reorder_window=0)
    with pytest.raises(ValueError, match="reorder_window"):
        DRAMSchedConfig(reorder_window=1024)
    with pytest.raises(ValueError, match="starvation_cap"):
        DRAMSchedConfig(starvation_cap=0)
    with pytest.raises(ValueError, match="t_rfc"):
        DRAMSchedConfig(t_rfc=-1)
    with pytest.raises(ValueError, match="refresh longer"):
        DRAMSchedConfig(t_rfc=200, t_refi=100)
    with pytest.raises(ValueError, match="refresh longer"):
        # t_rfc == t_refi would refresh forever between two issues
        DRAMSchedConfig(t_rfc=100, t_refi=100)
    assert DRAMSchedConfig(policy="fifo", reorder_window=64) \
        .effective_window == 1
    assert DRAMSchedConfig(policy="frfcfs", reorder_window=64) \
        .effective_window == 64


def test_reorder_window_costs_vmem():
    small = MemoryControllerConfig()
    big = dataclasses.replace(
        small, dram_sched=DRAMSchedConfig(policy="frfcfs",
                                          reorder_window=256))
    assert big.vmem_footprint_bytes() > small.vmem_footprint_bytes()


# ---------------------------------------------------------------------------
# Pipeline / channels integration
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 500),
                          st.integers(0, 1)),
                min_size=0, max_size=200),
       st.sampled_from([1, 4]),
       st.sampled_from(["frfcfs", "frfcfs_cap"]),
       st.booleans())
def test_property_pipeline_matches_seq_composition(reqs, num_channels,
                                                   policy, sched_on):
    """The DRAMServiceStage under a non-trivial DRAMSchedConfig is
    bit-identical to the request-at-a-time composition (per-channel
    arbiter + scheduler oracles + simulate_dram_sched_seq)."""
    rows = np.asarray([r[1] for r in reqs], np.int64)
    pe = np.asarray([r[0] for r in reqs], np.int64)
    rw = np.asarray([r[2] for r in reqs], np.int32)
    dsched = DRAMSchedConfig(policy=policy, reorder_window=8,
                             starvation_cap=4, t_rfc=30, t_refi=300)
    ccfg = ChannelConfig(num_channels=num_channels)
    scfg = SchedulerConfig(batch_size=16) if sched_on else None
    new = channels_mod.simulate_multiport_channels(
        pe, rows * 4096, rw, num_ports=4, channel_cfg=ccfg,
        sched_config=scfg, dram_sched=dsched)
    old = channels_mod.simulate_multiport_channels_seq(
        pe, rows * 4096, rw, num_ports=4, channel_cfg=ccfg,
        sched_config=scfg, dram_sched=dsched)
    assert new.makespan_fpga_cycles == old.makespan_fpga_cycles
    assert new.busy_fpga_cycles == old.busy_fpga_cycles
    assert new.row_hits == old.row_hits
    assert new.row_conflicts == old.row_conflicts
    assert new.first_accesses == old.first_accesses


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 400), min_size=0, max_size=200),
       st.sampled_from([1, 2, 4]))
def test_property_simulate_channels_sched_fast_vs_seq(rows, num_channels):
    addrs = np.asarray(rows, np.int64) * 4096
    dsched = DRAMSchedConfig(policy="frfcfs", reorder_window=8)
    ccfg = ChannelConfig(num_channels=num_channels)
    a = channels_mod.simulate_channels(addrs, DDR4_2400, ccfg,
                                       dram_sched=dsched)
    b = channels_mod.simulate_channels_seq(addrs, DDR4_2400, ccfg,
                                           dram_sched=dsched)
    assert a.makespan_fpga_cycles == b.makespan_fpga_cycles
    assert a.row_hits == b.row_hits
    assert a.row_conflicts == b.row_conflicts
    assert a.first_accesses == b.first_accesses


def test_simulate_respects_dram_sched_config(rng):
    """End to end through MemoryController.simulate: FR-FCFS with a
    deep window strictly beats FIFO service on a row-reuse-heavy
    irregular trace (engines off isolates the DRAM scheduler), and
    window=1 reproduces the FIFO numbers bit for bit."""
    rows = (rng.zipf(1.2, 20000) - 1) % 4096
    rw = (rng.random(20000) < 0.1).astype(np.int32)
    base = MemoryControllerConfig(
        scheduler=SchedulerConfig(enabled=False),
        cache=CacheConfig(enabled=False))
    fifo = MemoryController(base).simulate(None, rows, rw, 4096)
    w1 = MemoryController(dataclasses.replace(
        base, dram_sched=DRAMSchedConfig(policy="frfcfs",
                                         reorder_window=1))
    ).simulate(None, rows, rw, 4096)
    assert w1.makespan_fpga_cycles == fifo.makespan_fpga_cycles
    deep = MemoryController(dataclasses.replace(
        base, dram_sched=DRAMSchedConfig(policy="frfcfs",
                                         reorder_window=16))
    ).simulate(None, rows, rw, 4096)
    assert deep.makespan_fpga_cycles < fifo.makespan_fpga_cycles
    stage = deep.stage("dram_service")
    assert stage.info["sched_policy"] == "frfcfs"
    assert stage.info["reorder_window"] == 16


# ---------------------------------------------------------------------------
# Edge cases the fixed-point fast paths must pin (ISSUE 9): window
# covering the whole trace, single-request traces, all-miss streams,
# and the miss-heavy micro-step-budget boundary.
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 40), st.integers(0, 1)),
                min_size=1, max_size=96),
       st.sampled_from(["frfcfs", "frfcfs_cap"]),
       st.sampled_from([(0, 0), (5, 37)]),
       st.booleans())
def test_property_window_equals_trace_length(reqs, policy, refresh,
                                             use_rw):
    """reorder_window == len(trace): the whole stream is in flight at
    once — the deepest reordering the config admits for this trace."""
    t_rfc, t_refi = refresh
    addrs, rw = _trace(reqs)
    sched = DRAMSchedConfig(policy=policy, reorder_window=len(reqs),
                            starvation_cap=3, t_rfc=t_rfc, t_refi=t_refi)
    a = simulate_dram_sched(addrs, DDR4_2400, sched,
                            rw=rw if use_rw else None)
    b = simulate_dram_sched_seq(addrs, DDR4_2400, sched,
                                rw=rw if use_rw else None)
    _assert_sched_equal(a, b)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.integers(0, 1),
       st.sampled_from(["fifo", "frfcfs", "frfcfs_cap"]),
       st.sampled_from([1, 4, 64, 512]),
       st.sampled_from([(0, 0), (5, 37)]),
       st.booleans())
def test_property_single_request(row, is_write, policy, window, refresh,
                                 hbm):
    """A one-request trace costs exactly one first access + one burst
    under every policy/window/refresh combination, and the fast path
    agrees with the oracle bit for bit."""
    t_rfc, t_refi = refresh
    timings = HBM_V5E if hbm else DDR4_2400
    addrs = np.asarray([row], np.int64) * (timings.row_bytes // 2)
    rw = np.asarray([is_write], np.int32)
    sched = DRAMSchedConfig(policy=policy, reorder_window=window,
                            starvation_cap=2, t_rfc=t_rfc, t_refi=t_refi)
    a = simulate_dram_sched(addrs, timings, sched, rw=rw)
    b = simulate_dram_sched_seq(addrs, timings, sched, rw=rw)
    _assert_sched_equal(a, b)
    assert a.first_accesses == 1
    assert a.row_hits == 0 and a.row_conflicts == 0
    assert a.turnaround_dram_cycles == 0


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 180),
       st.sampled_from(["frfcfs", "frfcfs_cap"]),
       st.sampled_from([2, 8, 64]),
       st.booleans())
def test_property_all_miss_single_bank(n, policy, window, use_rw):
    """Strictly increasing rows in one bank: nothing to reorder, every
    access after the first conflicts, and no window/cap setting may
    change that — reordering can only exploit row hits, and there are
    none."""
    timings = DDR4_2400
    # stride num_banks rows -> same bank, all distinct rows
    addrs = (np.arange(n, dtype=np.int64) * timings.num_banks
             * timings.row_bytes)
    rw = (np.arange(n, dtype=np.int32) % 3 == 0).astype(np.int32)
    sched = DRAMSchedConfig(policy=policy, reorder_window=window,
                            starvation_cap=2)
    a = simulate_dram_sched(addrs, timings, sched,
                            rw=rw if use_rw else None)
    b = simulate_dram_sched_seq(addrs, timings, sched,
                                rw=rw if use_rw else None)
    _assert_sched_equal(a, b)
    assert a.first_accesses == 1
    assert a.row_hits == 0
    assert a.row_conflicts == n - 1


@settings(max_examples=15, deadline=None)
@given(st.integers(80, 220),
       st.sampled_from([2, 3, 8]),
       st.sampled_from([1, 2, 5]),
       st.booleans())
def test_property_micro_step_budget_boundary(n, window, cap, use_rw):
    """Miss-heavy capped traces around the fast path's python-step
    budget (MICRO=96 scalar steps per drain): the mode switch between
    the scalar drain and the bucketed scan must be invisible in the
    results. All-conflict single-bank streams maximize scalar steps, so
    drawing n across [80, 220] brackets the boundary from both sides."""
    timings = DDR4_2400
    rng = np.random.default_rng(n * 7 + window)
    # same-bank all-distinct rows with a few duplicates sprinkled in so
    # the window occasionally finds a hit right at the budget edge
    rows = np.arange(n, dtype=np.int64)
    dup = rng.integers(0, n, max(1, n // 16))
    rows[dup] = rows[(dup + 1) % n]
    addrs = rows * timings.num_banks * timings.row_bytes
    rw = rng.integers(0, 2, n).astype(np.int32)
    sched = DRAMSchedConfig(policy="frfcfs_cap", reorder_window=window,
                            starvation_cap=cap)
    a = simulate_dram_sched(addrs, timings, sched,
                            rw=rw if use_rw else None)
    b = simulate_dram_sched_seq(addrs, timings, sched,
                                rw=rw if use_rw else None)
    _assert_sched_equal(a, b)
