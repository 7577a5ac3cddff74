"""Open-loop serving (arrival-aware oracles + pipeline) — the property
harness that locks the serving model down.

Serving adds *time of arrival* to the order-dependent service model of
``test_dram_sched.py``: requests enter per-port FIFOs at their stamp, an
arbiter grants arrived heads into the reorder window at issue pace, and
idle gaps advance the clock (absorbing refreshes). Every property is
stated against the request-at-a-time spec
(:func:`repro.core.timing.simulate_arrivals_seq`) or against the
closed-loop simulators the serving model must degenerate to:

* vectorized path == oracle, bit for bit (every count, the issue order,
  the grant order, and every per-request completion stamp), over arrival
  process x ports x arbiter policy x DRAM policy x window x cap x
  refresh x rw;
* ``arrival_cycle == 0`` == the closed-loop world exactly: single-port
  == ``simulate_dram_sched_seq``, multi-port == ``arbitrate_ports_seq``
  composed with it, and the full pipeline (stage stats, makespan, port
  stats, per-channel issue permutation) == the pre-serving pipeline;
* sojourn invariants: sojourn >= own service time, non-negative
  queueing delay, p50 <= p95 <= p99, makespan >= max(arrival+sojourn);
* the starvation cap still bounds grant-order slip under load;
* per-port FIFO order survives arbitration (weak-consistency rule);
* idle accounting is exact: with refresh off, busy + idle == makespan.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pipeline as pl
from repro.core.channels import (ArbiterStats, arbitrate_ports_seq,
                                 simulate_serving_channels,
                                 simulate_serving_channels_seq)
from repro.core.config import ChannelConfig, DRAMSchedConfig
from repro.core.timing import (DDR4_2400, HBM_V5E, simulate_arrivals,
                               simulate_arrivals_seq,
                               simulate_dram_sched_seq)
from repro.data.synthetic import (bursty_arrivals, diurnal_arrivals,
                                  hog_victim_workload, poisson_arrivals)


def _trace(reqs, timings):
    addrs = np.asarray([r[0] for r in reqs], np.int64) \
        * (timings.row_bytes // 2)
    rw = np.asarray([r[1] for r in reqs], np.int32)
    gaps = np.asarray([r[2] for r in reqs], np.float64)
    pe = np.asarray([r[3] for r in reqs], np.int64)
    return addrs, rw, np.cumsum(gaps), pe


def _assert_serving_equal(a, b):
    assert a.total_fpga_cycles == b.total_fpga_cycles
    assert a.row_hits == b.row_hits
    assert a.row_conflicts == b.row_conflicts
    assert a.first_accesses == b.first_accesses
    assert a.n_refreshes == b.n_refreshes
    assert a.refresh_dram_cycles == b.refresh_dram_cycles
    assert a.turnaround_dram_cycles == b.turnaround_dram_cycles
    assert a.idle_dram_cycles == b.idle_dram_cycles
    np.testing.assert_array_equal(a.service_order, b.service_order)
    np.testing.assert_array_equal(a.grant_order, b.grant_order)
    np.testing.assert_array_equal(a.granted_port, b.granted_port)
    np.testing.assert_array_equal(a.completion_fpga_cycles,
                                  b.completion_fpga_cycles)
    np.testing.assert_array_equal(a.service_dram_cycles,
                                  b.service_dram_cycles)


def _slips(order: np.ndarray) -> np.ndarray:
    """slip[i] = number of younger entries issued before entry i
    (indices are positions in the *grant* order)."""
    order = np.asarray(order, np.int64)
    n = order.shape[0]
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)
    younger = np.arange(n)[None, :] > np.arange(n)[:, None]
    earlier = pos[None, :] < pos[:, None]
    return (younger & earlier).sum(axis=1)


# ---------------------------------------------------------------------------
# Vectorized path == request-at-a-time oracle (the headline identity)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 40),      # row
                          st.integers(0, 1),       # rw
                          st.sampled_from([0, 0, 1, 3, 9, 40]),  # gap
                          st.integers(0, 3)),      # port
                min_size=0, max_size=180),
       st.sampled_from(["fifo", "frfcfs", "frfcfs_cap"]),
       st.sampled_from([1, 2, 4, 16, 64]),
       st.sampled_from([1, 2, 8]),
       st.sampled_from([(0, 0), (0, 37), (30, 100), (30, 500)]),
       st.sampled_from([(1, "round_robin", None),
                        (2, "round_robin", None),
                        (4, "priority", None),
                        (3, "weighted", (3, 1, 2)),
                        (4, "weighted", (5, 1, 1, 2))]),
       st.booleans(),
       st.booleans())
def test_property_serving_fast_matches_oracle(reqs, policy, window, cap,
                                              refresh, arb, use_rw, hbm):
    t_rfc, t_refi = refresh
    nports, apol, weights = arb
    timings = HBM_V5E if hbm else DDR4_2400
    addrs, rw, arr, pe = _trace(reqs, timings)
    pe = pe % nports
    sched = DRAMSchedConfig(policy=policy, reorder_window=window,
                            starvation_cap=cap, t_rfc=t_rfc,
                            t_refi=t_refi)
    kw = dict(rw=rw if use_rw else None, arrival_fpga=arr,
              pe_id=pe if nports > 1 else None, num_ports=nports,
              arb_policy=apol, weights=weights)
    a = simulate_arrivals_seq(addrs, timings, sched, **kw)
    b = simulate_arrivals(addrs, timings, sched, **kw)
    _assert_serving_equal(a, b)
    assert np.array_equal(np.sort(a.service_order), np.arange(len(reqs)))
    assert np.array_equal(np.sort(a.grant_order), np.arange(len(reqs)))


# ---------------------------------------------------------------------------
# Closed-loop degeneracy: arrival_cycle == 0 is the pre-serving world
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 50), st.integers(0, 1),
                          st.sampled_from([0]), st.sampled_from([0])),
                min_size=0, max_size=200),
       st.sampled_from(["fifo", "frfcfs", "frfcfs_cap"]),
       st.sampled_from([1, 3, 16, 64]),
       st.sampled_from([2, 8]),
       st.sampled_from([(0, 0), (30, 120)]),
       st.booleans(),
       st.booleans())
def test_zero_arrivals_degenerate_to_dram_sched(reqs, policy, window, cap,
                                                refresh, use_rw, none_arr):
    """Single port, all-zero stamps: the serving oracle *is*
    ``simulate_dram_sched_seq`` — same makespan, counts and issue
    order — whether arrivals are omitted or explicit zeros."""
    t_rfc, t_refi = refresh
    addrs, rw, _, _ = _trace(reqs, DDR4_2400)
    sched = DRAMSchedConfig(policy=policy, reorder_window=window,
                            starvation_cap=cap, t_rfc=t_rfc,
                            t_refi=t_refi)
    arr = None if none_arr else np.zeros(len(reqs))
    a = simulate_arrivals_seq(addrs, DDR4_2400, sched,
                              rw=rw if use_rw else None, arrival_fpga=arr)
    b = simulate_dram_sched_seq(addrs, DDR4_2400, sched,
                                rw=rw if use_rw else None)
    assert a.total_fpga_cycles == b.total_fpga_cycles
    assert (a.row_hits, a.row_conflicts, a.first_accesses,
            a.n_refreshes, a.turnaround_dram_cycles) == \
           (b.row_hits, b.row_conflicts, b.first_accesses,
            b.n_refreshes, b.turnaround_dram_cycles)
    np.testing.assert_array_equal(a.service_order, b.service_order)
    assert a.idle_dram_cycles == 0.0


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 50), st.integers(0, 1),
                          st.sampled_from([0]), st.integers(0, 3)),
                min_size=1, max_size=200),
       st.sampled_from([("round_robin", None), ("priority", None),
                        ("weighted", (4, 1, 2, 1))]),
       st.sampled_from([1, 4, 32]),
       st.booleans())
def test_zero_arrivals_degenerate_to_arbiter_composition(reqs, arb, window,
                                                         use_rw):
    """Multi port, all-zero stamps: the coupled admission loop grants
    exactly the saturated arbiter's permutation, and service equals the
    closed-loop scheduler run on the arbitrated stream."""
    apol, weights = arb
    nports = 4
    addrs, rw, _, pe = _trace(reqs, DDR4_2400)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=window)
    a = simulate_arrivals_seq(addrs, DDR4_2400, sched,
                              rw=rw if use_rw else None,
                              pe_id=pe, num_ports=nports,
                              arb_policy=apol, weights=weights)
    perm, stats = arbitrate_ports_seq(pe, num_ports=nports, policy=apol,
                                      weights=weights)
    b = simulate_dram_sched_seq(addrs[perm], DDR4_2400, sched,
                                rw=None if not use_rw else rw[perm])
    assert a.total_fpga_cycles == b.total_fpga_cycles
    np.testing.assert_array_equal(a.grant_order, perm)
    np.testing.assert_array_equal(a.service_order, perm[b.service_order])
    np.testing.assert_array_equal(
        ArbiterStats.from_grant_order(a.granted_port, nports).grants,
        stats.grants)


@pytest.mark.parametrize("nc", [1, 4])
@pytest.mark.parametrize("policy,window", [("fifo", 1), ("frfcfs", 16),
                                           ("frfcfs_cap", 32)])
@pytest.mark.parametrize("use_rw", [False, True])
def test_pipeline_degeneracy_bit_identical(nc, policy, window, use_rw):
    """The tentpole acceptance property: an ``arrival_cycle == 0``
    stream forced through the serving datapath reproduces the pre-PR
    pipeline bit for bit — makespan, every stage's cycles and request
    counts, port stats, and the per-channel issue permutation."""
    rng = np.random.default_rng(nc * 100 + window)
    n = 600
    addrs = rng.integers(0, 1 << 20, n).astype(np.int64) * 64
    rw = (rng.random(n) < 0.3).astype(np.int32) if use_rw else None
    pe = rng.integers(0, 4, n)
    sched = DRAMSchedConfig(policy=policy, reorder_window=window,
                            starvation_cap=8, t_refi=9363, t_rfc=420)

    def run(arrival, open_loop):
        stream = pl.RequestStream.from_addrs(addrs, rw, pe_id=pe,
                                             arrival_cycle=arrival)
        ctx = pl.PipelineContext(
            channels=ChannelConfig(num_channels=nc), scheduler=None,
            cache=None, timings=DDR4_2400, dram_sched=sched,
            open_loop=open_loop)
        stages = pl.default_stages(ctx, ports=4,
                                   arbiter_policy="weighted",
                                   weights=[4, 1, 2, 1], cache=False)
        return pl.run_pipeline(stream, ctx, stages)

    a = run(np.zeros(n), open_loop=True)    # serving datapath, forced
    b = run(None, open_loop=None)           # legacy closed-loop pipeline
    assert b.serving is None and a.serving is not None
    assert a.makespan_fpga_cycles == b.makespan_fpga_cycles
    assert a.dram_makespan_fpga_cycles == b.dram_makespan_fpga_cycles
    for sa, sb in zip(a.stages, b.stages):
        assert (sa.name, sa.cycles, sa.in_requests, sa.out_requests) == \
               (sb.name, sb.cycles, sb.in_requests, sb.out_requests)
    np.testing.assert_array_equal(a.port_stats.grants, b.port_stats.grants)
    np.testing.assert_array_equal(a.port_stats.stall_slots,
                                  b.port_stats.stall_slots)
    assert a.requests_per_channel == b.requests_per_channel
    # per-channel issue permutation: serving issues grant_order[order_b]
    # where order_b is the legacy post-arbitration issue order
    for pa, pb in zip(a.per_channel, b.per_channel):
        assert pa.total_fpga_cycles == pb.total_fpga_cycles
        assert (pa.row_hits, pa.row_conflicts, pa.first_accesses) == \
               (pb.row_hits, pb.row_conflicts, pb.first_accesses)
        np.testing.assert_array_equal(pa.service_order,
                                      pa.grant_order[pb.service_order])
    # degenerate sojourns: completion == sojourn (arrival 0), max ==
    # makespan, and the serving view is self-consistent
    s = a.serving
    assert a.makespan_fpga_cycles == float(s.completion_fpga_cycles.max())


# ---------------------------------------------------------------------------
# Sojourn invariants
# ---------------------------------------------------------------------------

def _serving_result(seed, gen, rate, nports=4, policy="weighted"):
    rng = np.random.default_rng(seed)
    n = 2500
    addrs = rng.integers(0, 1 << 20, n).astype(np.int64) * 64
    rw = (rng.random(n) < 0.25).astype(np.int32)
    pe = rng.integers(0, nports, n)
    arr = gen(rng, n, rate)
    stream = pl.RequestStream.from_addrs(addrs, rw, pe_id=pe,
                                         arrival_cycle=arr)
    ctx = pl.PipelineContext(
        channels=ChannelConfig(num_channels=2), scheduler=None,
        cache=None, timings=DDR4_2400, ctrl_overhead_cycles=10.0,
        dram_sched=DRAMSchedConfig(policy="frfcfs_cap", reorder_window=16,
                                   starvation_cap=8, t_refi=9363,
                                   t_rfc=420))
    stages = pl.default_stages(ctx, ports=nports, arbiter_policy=policy,
                               weights=[4, 1, 1, 1], cache=False)
    return pl.run_pipeline(stream, ctx, stages)


@pytest.mark.parametrize("gen,rate", [
    (poisson_arrivals, 0.3), (poisson_arrivals, 0.02),
    (bursty_arrivals, 0.1), (diurnal_arrivals, 0.1)])
def test_sojourn_invariants(gen, rate):
    res = _serving_result(7, gen, rate)
    s = res.serving
    soj = s.sojourn_fpga_cycles
    assert (soj >= s.service_fpga_cycles - 1e-9).all()
    assert (s.queueing_fpga_cycles >= -1e-9).all()
    assert s.p50_sojourn <= s.p95_sojourn <= s.p99_sojourn \
        <= s.worst_sojourn
    assert res.makespan_fpga_cycles >= \
        float((s.arrival_fpga_cycles + soj).max()) - 1e-9
    assert s.sustained_req_per_cycle > 0
    assert set(s.per_port) == {0, 1, 2, 3}
    assert sum(d["n"] for d in s.per_port.values()) == res.n_requests


def test_starvation_cap_bounds_grant_order_slip():
    """Under saturating load, frfcfs_cap still bounds how many younger
    *granted* requests may pass any request (the closed-loop slip bound
    restated in grant space)."""
    rng = np.random.default_rng(11)
    n = 1200
    cap = 4
    addrs = rng.integers(0, 1 << 18, n).astype(np.int64) * 64
    arr = poisson_arrivals(rng, n, 2.0)          # far beyond capacity
    pe = rng.integers(0, 2, n)
    res = simulate_arrivals(
        addrs, DDR4_2400,
        DRAMSchedConfig(policy="frfcfs_cap", reorder_window=32,
                        starvation_cap=cap),
        arrival_fpga=arr, pe_id=pe, num_ports=2)
    inv = np.empty(n, np.int64)
    inv[res.grant_order] = np.arange(n)
    order_in_grant_space = inv[res.service_order]
    assert _slips(order_in_grant_space).max() <= cap


def test_per_port_fifo_order_preserved():
    rng = np.random.default_rng(3)
    n = 2000
    addrs = rng.integers(0, 1 << 20, n).astype(np.int64) * 64
    arr = bursty_arrivals(rng, n, 0.2)
    pe = rng.integers(0, 4, n)
    for policy, w in [("round_robin", None), ("priority", None),
                      ("weighted", (4, 2, 1, 1))]:
        res = simulate_arrivals(
            addrs, DDR4_2400,
            DRAMSchedConfig(policy="frfcfs", reorder_window=16),
            arrival_fpga=arr, pe_id=pe, num_ports=4,
            arb_policy=policy, weights=w)
        for p in range(4):
            mine = res.grant_order[pe[res.grant_order] == p]
            assert (np.diff(mine) > 0).all()


def test_idle_gap_is_exact():
    """An isolated late request completes at arrival + its own service
    time, and with refresh off the clock decomposes exactly into busy
    + idle."""
    t = DDR4_2400
    addrs = np.array([0, t.row_bytes * t.num_banks * 4]) * 1
    arr = np.array([0.0, 5000.0])
    res = simulate_arrivals(addrs, t, DRAMSchedConfig(),
                            arrival_fpga=arr)
    np.testing.assert_allclose(
        res.completion_fpga_cycles[1],
        5000.0 + res.service_dram_cycles[1] * t.clock_ratio)
    rng = np.random.default_rng(0)
    n = 800
    a2 = rng.integers(0, 1 << 16, n).astype(np.int64) * 64
    arr2 = poisson_arrivals(rng, n, 0.01)        # mostly idle
    r2 = simulate_arrivals(a2, t, DRAMSchedConfig(policy="frfcfs",
                                                  reorder_window=8),
                           arrival_fpga=arr2)
    busy = int(r2.service_dram_cycles.sum())
    np.testing.assert_allclose(
        r2.total_fpga_cycles / t.clock_ratio,
        busy + r2.idle_dram_cycles)
    assert r2.idle_dram_cycles > 0


def test_arrival_validation():
    with pytest.raises(ValueError, match="arrival"):
        simulate_arrivals(np.array([0, 64]), DDR4_2400, DRAMSchedConfig(),
                          arrival_fpga=np.array([0.0, -1.0]))
    with pytest.raises(ValueError, match="arrival"):
        simulate_arrivals(np.array([0, 64]), DDR4_2400, DRAMSchedConfig(),
                          arrival_fpga=np.array([0.0]))
    with pytest.raises(ValueError):
        pl.RequestStream.from_addrs(np.array([0, 64]),
                                    arrival_cycle=np.array([0.0, np.inf]))


# ---------------------------------------------------------------------------
# Channels-layer composition + generators
# ---------------------------------------------------------------------------

def test_serving_channels_fast_matches_seq_oracle():
    rng = np.random.default_rng(9)
    n = 1500
    addrs = rng.integers(0, 1 << 22, n).astype(np.int64) * 64
    rw = (rng.random(n) < 0.3).astype(np.int32)
    arr = poisson_arrivals(rng, n, 0.15)
    pe = rng.integers(0, 4, n)
    kw = dict(pe_id=pe, num_ports=4, policy="weighted",
              weights=[4, 2, 1, 1],
              channel_cfg=ChannelConfig(num_channels=4, policy="xor"),
              dram_sched=DRAMSchedConfig(policy="frfcfs_cap",
                                         reorder_window=16,
                                         starvation_cap=8,
                                         t_refi=9363, t_rfc=420))
    a = simulate_serving_channels_seq(addrs, arr, rw, **kw)
    b = simulate_serving_channels(addrs, arr, rw, **kw)
    assert a.makespan_fpga_cycles == b.makespan_fpga_cycles
    assert (a.row_hits, a.row_conflicts, a.first_accesses) == \
           (b.row_hits, b.row_conflicts, b.first_accesses)
    np.testing.assert_array_equal(a.completion_fpga_cycles,
                                  b.completion_fpga_cycles)
    np.testing.assert_array_equal(a.port_stats.grants, b.port_stats.grants)


def test_arrival_generators_are_calibrated_and_deterministic():
    n = 60000
    for gen in (poisson_arrivals, bursty_arrivals, diurnal_arrivals):
        a = gen(np.random.default_rng(0), n, 0.05)
        b = gen(np.random.default_rng(0), n, 0.05)
        np.testing.assert_array_equal(a, b)      # stream-stable draws
        assert (np.diff(a) >= 0).all() and a[0] >= 0
        rate = n / a[-1]
        assert 0.045 < rate < 0.055, (gen.__name__, rate)
    rows, rw, pe, arr = hog_victim_workload(
        np.random.default_rng(1), n_victim=500, n_hog=2000,
        victim_rate=0.01, hog_rate=0.2)
    assert (np.diff(arr) >= 0).all()
    assert set(np.unique(pe)) == {0, 1}
    assert (rw[pe == 0] == 0).all()              # victim is read-only


def test_controller_simulate_serving_entry():
    """``MemoryController.simulate(..., arrival_cycle=...)`` runs the
    drop-free serving subset and reports sojourns; the same call
    without stamps keeps the legacy closed-loop result shape."""
    from repro.core.config import MemoryControllerConfig
    from repro.core.controller import MemoryController

    rng = np.random.default_rng(2)
    rows, rw, pe, arr = hog_victim_workload(
        rng, n_victim=300, n_hog=1200, victim_rate=0.02, hog_rate=0.3)
    mc = MemoryController(MemoryControllerConfig(num_pes=2))
    res = mc.simulate(pe, rows, rw, 4096, arbiter_policy="weighted",
                      weights=[4, 1], arrival_cycle=arr)
    assert res.serving is not None
    assert res.stage("cache_filter") is None     # drop-free subset
    assert res.stage("batch_scheduler") is None
    assert res.serving.p99_sojourn >= res.serving.p50_sojourn > 0
    closed = mc.simulate(pe, rows, rw, 4096)
    assert closed.serving is None
