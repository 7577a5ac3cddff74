"""Multi-port / multi-channel front end vs its sequential oracles.

Every stage of the new front end keeps a request-at-a-time sibling:
``simulate_channels_seq`` (global walk over interleaved per-channel
bank/turnaround state), ``arbitrate_ports_seq`` (grant-per-slot loop over
per-port FIFOs), and the ``*_seq`` compositions of the full
pipeline (seq arbiter + seq scheduler + per-request DRAM walk). These
property tests assert the fast paths are *bit-identical* across channel
counts, mapping policies, arbiter policies, timings presets and
multi-PE traces — the same contract as the set-parallel trace engine.
"""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.channels import (AddressMap, arbitrate_ports,
                                 arbitrate_ports_seq, arbiter_fill_cycles,
                                 per_port_order_preserved,
                                 schedule_and_simulate_channels,
                                 schedule_and_simulate_channels_seq,
                                 simulate_channels, simulate_channels_seq,
                                 simulate_multiport_channels,
                                 simulate_multiport_channels_seq)
from repro.core.config import (ChannelConfig, MemoryControllerConfig,
                               SchedulerConfig)
from repro.core.controller import MemoryController
from repro.core.timing import DDR4_2400, HBM_V5E, simulate_dram_access

POLICIES = ("row_interleave", "block_interleave", "xor")


def _assert_channel_results_equal(a, b):
    assert a.makespan_fpga_cycles == b.makespan_fpga_cycles
    assert a.busy_fpga_cycles == b.busy_fpga_cycles
    assert a.arbitration_cycles == b.arbitration_cycles
    assert a.requests_per_channel == b.requests_per_channel
    assert [dataclasses.asdict(r) for r in a.per_channel] == \
        [dataclasses.asdict(r) for r in b.per_channel]
    if a.port_stats is not None or b.port_stats is not None:
        np.testing.assert_array_equal(a.port_stats.grants,
                                      b.port_stats.grants)
        np.testing.assert_array_equal(a.port_stats.stall_slots,
                                      b.port_stats.stall_slots)
        assert a.port_stats.fairness == b.port_stats.fairness


# ---------------------------------------------------------------------------
# Address map
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("num_channels", [1, 2, 4, 8])
def test_address_map_is_bijective(policy, num_channels, rng):
    cfg = ChannelConfig(num_channels=num_channels, policy=policy,
                        interleave_bytes=256)
    amap = AddressMap(cfg, DDR4_2400)
    addrs = np.unique(rng.integers(0, 1 << 26, 4096))
    ch = amap.channel_of(addrs)
    local = amap.local_addr(addrs)
    assert int(ch.min()) >= 0 and int(ch.max()) < num_channels
    # distinct addresses never collide in (channel, local)
    key = ch * (1 << 40) + local
    assert np.unique(key).size == addrs.size
    # decompose agrees with the per-channel open-row decode
    c2, bank, row = amap.decompose(addrs)
    np.testing.assert_array_equal(c2, ch)
    np.testing.assert_array_equal(bank, DDR4_2400.bank_of(local))
    np.testing.assert_array_equal(row, DDR4_2400.row_of(local))


def test_xor_policy_breaks_stride_camping():
    """A stride of granularity*num_channels camps on one channel under
    plain block interleave; the XOR fold spreads it."""
    cfg_block = ChannelConfig(num_channels=4, policy="block_interleave",
                              interleave_bytes=256)
    cfg_xor = ChannelConfig(num_channels=4, policy="xor",
                            interleave_bytes=256)
    addrs = np.arange(256, dtype=np.int64) * (256 * 4)
    camped = AddressMap(cfg_block, DDR4_2400).channel_of(addrs)
    spread = AddressMap(cfg_xor, DDR4_2400).channel_of(addrs)
    assert np.unique(camped).size == 1
    assert np.unique(spread).size == 4


# ---------------------------------------------------------------------------
# Channel-parallel simulator vs sequential oracle
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5000), st.integers(0, 1)),
                min_size=0, max_size=400),
       st.sampled_from([1, 2, 4, 8]),
       st.sampled_from(POLICIES),
       st.booleans(),
       st.booleans())
def test_property_channel_sim_identical(reqs, num_channels, policy,
                                        use_rw, hbm):
    timings = HBM_V5E if hbm else DDR4_2400
    cfg = ChannelConfig(num_channels=num_channels, policy=policy,
                        interleave_bytes=512)
    addrs = np.asarray([r[0] * 1024 for r in reqs], np.int64)
    rw = np.asarray([r[1] for r in reqs], np.int32) if use_rw else None
    fast = simulate_channels(addrs, timings, cfg, rw=rw)
    ref = simulate_channels_seq(addrs, timings, cfg, rw=rw)
    _assert_channel_results_equal(fast, ref)


def test_single_channel_matches_plain_simulator(rng):
    """C=1 is the paper's single-interface design: the channel layer must
    cost exactly what simulate_dram_access costs."""
    addrs = rng.integers(0, 1 << 24, 2000).astype(np.int64)
    rw = rng.integers(0, 2, 2000).astype(np.int32)
    plain = simulate_dram_access(addrs, DDR4_2400, rw=rw)
    chan = simulate_channels(addrs, DDR4_2400, ChannelConfig(), rw=rw)
    assert chan.makespan_fpga_cycles == plain.total_fpga_cycles
    assert chan.row_hits == plain.row_hits
    assert chan.row_conflicts == plain.row_conflicts


def test_makespan_bounded_by_single_channel(rng):
    """Splitting a trace over C channels can never cost more wall-clock
    than one channel serving everything (banks only get less loaded)."""
    addrs = (rng.integers(0, 1 << 16, 20000) * 64).astype(np.int64)
    one = simulate_channels(addrs, DDR4_2400, ChannelConfig())
    for c in (2, 4, 8):
        cfg = ChannelConfig(num_channels=c)
        multi = simulate_channels(addrs, DDR4_2400, cfg)
        assert multi.makespan_fpga_cycles <= one.makespan_fpga_cycles
        assert multi.busy_fpga_cycles <= one.busy_fpga_cycles * 1.5


# ---------------------------------------------------------------------------
# Arbiter vs sequential oracle
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 7), min_size=0, max_size=300),
       st.sampled_from(["round_robin", "priority", "weighted"]),
       st.sampled_from([1, 2, 3]))
def test_property_arbiter_identical(pe_ids, policy, wseed):
    num_ports = 8
    rng = np.random.default_rng(wseed)
    weights = rng.integers(1, 5, num_ports).tolist() \
        if policy == "weighted" else None
    pe = np.asarray(pe_ids, np.int64)
    p_fast, s_fast = arbitrate_ports(pe, num_ports=num_ports,
                                     policy=policy, weights=weights)
    p_seq, s_seq = arbitrate_ports_seq(pe, num_ports=num_ports,
                                       policy=policy, weights=weights)
    np.testing.assert_array_equal(p_fast, p_seq)
    np.testing.assert_array_equal(s_fast.grants, s_seq.grants)
    np.testing.assert_array_equal(s_fast.stall_slots, s_seq.stall_slots)
    assert s_fast.fairness == s_seq.fairness
    # grant order is a permutation and per-port arrival order survives
    assert sorted(p_fast.tolist()) == list(range(pe.size))
    for p in range(num_ports):
        mine = p_fast[pe[p_fast] == p]
        assert (np.diff(mine) > 0).all()


def test_round_robin_interleaves_and_priority_drains():
    pe = np.asarray([0, 0, 0, 1, 1, 2], np.int64)
    p_rr, _ = arbitrate_ports(pe, num_ports=3, policy="round_robin")
    np.testing.assert_array_equal(pe[p_rr], [0, 1, 2, 0, 1, 0])
    p_pr, _ = arbitrate_ports(pe, num_ports=3, policy="priority")
    np.testing.assert_array_equal(pe[p_pr], [0, 0, 0, 1, 1, 2])


def test_weighted_gives_heavy_port_consecutive_grants():
    pe = np.asarray([0, 1] * 6, np.int64)
    p, stats = arbitrate_ports(pe, num_ports=2, policy="weighted",
                               weights=[1, 3])
    np.testing.assert_array_equal(pe[p][:8], [0, 1, 1, 1, 0, 1, 1, 1])
    assert stats.grants.tolist() == [6, 6]


def test_arbiter_stats_stalls_and_fairness():
    # port 1 waits one slot for each of port 0's interleaved grants
    pe = np.asarray([0, 1, 0, 1], np.int64)
    _, stats = arbitrate_ports(pe, num_ports=2, policy="round_robin")
    assert stats.grants.tolist() == [2, 2]
    assert stats.stall_slots.tolist() == [1, 2]
    assert stats.fairness == 1.0
    _, skew = arbitrate_ports(np.asarray([0] * 9 + [1], np.int64),
                              num_ports=2, policy="priority")
    assert skew.fairness < 0.7
    assert arbiter_fill_cycles(1) == 0
    assert arbiter_fill_cycles(8) == 3


# ---------------------------------------------------------------------------
# Full front end: arbiter + mapping + scheduler + channels
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 800),
                          st.integers(0, 1)),
                min_size=0, max_size=250),
       st.sampled_from([1, 2, 4]),
       st.sampled_from(POLICIES),
       st.sampled_from(["round_robin", "priority", "weighted"]))
def test_property_multiport_pipeline_identical(reqs, num_channels,
                                               map_policy, arb_policy):
    """End-to-end bit-identity: vectorized arbiter + vectorized scheduler
    + channel-parallel simulation vs the all-sequential composition."""
    pe = np.asarray([r[0] for r in reqs], np.int64)
    addrs = np.asarray([r[1] * 4096 for r in reqs], np.int64)
    rw = np.asarray([r[2] for r in reqs], np.int32)
    weights = [1, 3, 2, 1] if arb_policy == "weighted" else None
    kwargs = dict(num_ports=4, policy=arb_policy, weights=weights,
                  timings=DDR4_2400,
                  channel_cfg=ChannelConfig(num_channels=num_channels,
                                            policy=map_policy),
                  sched_config=SchedulerConfig(batch_size=16))
    fast = simulate_multiport_channels(pe, addrs, rw, **kwargs)
    ref = simulate_multiport_channels_seq(pe, addrs, rw, **kwargs)
    _assert_channel_results_equal(fast, ref)


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 600), st.integers(0, 1)),
                min_size=0, max_size=300),
       st.sampled_from([1, 2, 8]),
       st.booleans())
def test_property_scheduled_channel_pipeline_identical(reqs, num_channels,
                                                       coalesce):
    addrs = np.asarray([r[0] * 4096 for r in reqs], np.int64)
    rw = np.asarray([r[1] for r in reqs], np.int32)
    kwargs = dict(sched_config=SchedulerConfig(batch_size=32),
                  timings=DDR4_2400,
                  channel_cfg=ChannelConfig(num_channels=num_channels),
                  coalesce_writes=coalesce)
    fast = schedule_and_simulate_channels(addrs, rw, **kwargs)
    ref = schedule_and_simulate_channels_seq(addrs, rw, **kwargs)
    _assert_channel_results_equal(fast, ref)


def test_multiport_preserves_per_port_order_within_channel(rng):
    """The weak-consistency prerequisite the arbiter provides: inside
    every channel queue, each port's requests appear in arrival order
    (across channels a port's requests may complete out of order — that
    is the channel parallelism being modeled)."""
    n = 2000
    pe = rng.integers(0, 8, n)
    addrs = (rng.integers(0, 1 << 14, n) * 512).astype(np.int64)
    for policy, w in (("round_robin", None), ("priority", None),
                      ("weighted", [1, 2, 1, 4, 1, 1, 2, 1])):
        assert per_port_order_preserved(
            pe, addrs, num_ports=8,
            channel_cfg=ChannelConfig(num_channels=4),
            policy=policy, weights=w)


def test_controller_multichannel_makespan_improves(rng):
    """modeled_access_time with 4 channels beats the single-interface
    controller on an irregular trace, and the multiport entry point
    reports coherent stats."""
    rows = rng.integers(0, 1 << 14, 30000)
    rw = rng.integers(0, 2, 30000)
    pe = rng.integers(0, 8, 30000)
    mc1 = MemoryController(MemoryControllerConfig())
    mc4 = MemoryController(MemoryControllerConfig(
        channels=ChannelConfig(num_channels=4)))
    t1 = mc1.modeled_access_time(rows, rw, 512).total_fpga_cycles
    t4 = mc4.modeled_access_time(rows, rw, 512).total_fpga_cycles
    assert t4 < t1
    full = mc4.modeled_channel_access_time(rows, rw, 512)
    assert len(full.per_channel) == 4
    assert sum(full.requests_per_channel) == 30000
    mp = mc4.modeled_multiport_access_time(pe, rows, rw, 512)
    assert mp.port_stats.grants.sum() == 30000
    assert 0.9 < mp.port_stats.fairness <= 1.0
    assert mp.arbitration_cycles == arbiter_fill_cycles(8)
