"""RAS / fault-injection layer (ARCHITECTURE §10) — the property
harness that locks the fault model down.

Everything is stated against the request-at-a-time spec
(:func:`repro.core.timing.simulate_faults_seq`) or against the
fault-free simulators the RAS layer must degenerate to:

* scalar and vectorized hash draws are the same wrapping arithmetic,
  bit for bit;
* the same (seed, channel) reproduces the same storm — determinism;
* fast path == oracle under full storms (every count, stamp, attempt
  and FaultStats field), over BER x ECC x replay x degradation knobs;
* an inactive config is *bit-identical* to the pre-RAS world: the
  sequential oracle against ``simulate_arrivals_seq``, and the full
  pipeline against the checked-in golden records (schema included);
* replay is bounded: attempts <= max_replays + 1, and a request either
  completes or is flagged dropped — never silently lost;
* a retired row never serves again: after retirement every later
  access to the natural row issues against its spare;
* outage windows stall but drop nothing; failed-channel remap keeps
  the AddressMap a bijection and the dead channel empty.
"""

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import faults as F
from repro.core.config import (ChannelConfig, DRAMSchedConfig, FaultConfig,
                               MemoryControllerConfig)
from repro.core.controller import MemoryController
from repro.core.faults import SPARE_ROW_BASE, FaultStats
from repro.core.timing import (DDR4_2400, simulate_arrivals_seq,
                               simulate_faults, simulate_faults_seq)
from repro.core.trace_engine import simulate_faults_fast

STORM = FaultConfig(seed=2, transient_ber=0.01, weak_row_fraction=0.02,
                    weak_row_ber=0.5, due_fraction=0.3, max_replays=3,
                    backoff_clocks=64, row_retire_threshold=2,
                    refresh_escalate_threshold=25,
                    outage_windows=((0, 2000, 5000),))


def _trace(seed, n=1500, n_rows=600, ports=3, rate=0.08):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n_rows, n).astype(np.int64)
    addrs = rows * DDR4_2400.row_bytes
    rw = (rng.random(n) < 0.3).astype(np.int32)
    pe = rng.integers(0, ports, n).astype(np.int64)
    arr = np.cumsum(-np.log(np.clip(rng.random(n), 1e-12, 1.0)) / rate)
    return addrs, rw, pe, arr


def _assert_results_equal(a, b):
    assert a.total_fpga_cycles == b.total_fpga_cycles
    assert (a.row_hits, a.row_conflicts, a.first_accesses) == \
        (b.row_hits, b.row_conflicts, b.first_accesses)
    assert (a.n_refreshes, a.turnaround_dram_cycles) == \
        (b.n_refreshes, b.turnaround_dram_cycles)
    assert a.idle_dram_cycles == b.idle_dram_cycles
    np.testing.assert_array_equal(a.service_order, b.service_order)
    np.testing.assert_array_equal(a.grant_order, b.grant_order)
    np.testing.assert_array_equal(a.completion_fpga_cycles,
                                  b.completion_fpga_cycles)
    np.testing.assert_array_equal(a.service_dram_cycles,
                                  b.service_dram_cycles)


def _assert_fault_results_equal(a, b):
    _assert_results_equal(a, b)
    np.testing.assert_array_equal(a.attempts, b.attempts)
    np.testing.assert_array_equal(a.dropped, b.dropped)
    assert a.fault.as_dict() == b.fault.as_dict()


# ---------------------------------------------------------------------------
# The hash: scalar spec == vectorized, and it is deterministic
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**32), st.integers(0, 7),
       st.lists(st.integers(0, 2**40), min_size=1, max_size=50),
       st.integers(1, 5))
@settings(max_examples=50, deadline=None)
def test_property_scalar_and_vector_draws_identical(seed, ch, idxs, att):
    fc = FaultConfig(seed=seed, transient_ber=0.5, weak_row_fraction=0.5,
                     weak_row_ber=0.1)
    idx = np.asarray(idxs, np.int64)
    vec = F.error_uniforms(fc, ch, idx, att)
    for k, i in enumerate(idxs):
        assert vec[k] == F.error_uniform(fc, ch, i, att)
    wvec = F.weak_rows(fc, ch, idx)
    for k, i in enumerate(idxs):
        assert wvec[k] == F.weak_row(fc, ch, i)


def test_draws_decorrelate_across_streams():
    """Different channels / attempts / seeds see different storms, and
    every uniform is in [0, 1)."""
    fc = FaultConfig(seed=3, transient_ber=0.5)
    idx = np.arange(4000)
    a = F.error_uniforms(fc, 0, idx, 1)
    assert ((0.0 <= a) & (a < 1.0)).all()
    assert a.mean() == pytest.approx(0.5, abs=0.05)
    for other in (F.error_uniforms(fc, 1, idx, 1),
                  F.error_uniforms(fc, 0, idx, 2),
                  F.error_uniforms(dataclasses.replace(fc, seed=4),
                                   0, idx, 1)):
        assert not np.array_equal(a, other)
    np.testing.assert_array_equal(a, F.error_uniforms(fc, 0, idx, 1))


def test_spare_rows_are_never_weak():
    fc = FaultConfig(weak_row_fraction=1.0, weak_row_ber=1.0)
    assert F.weak_row(fc, 0, 5)
    assert not F.weak_row(fc, 0, SPARE_ROW_BASE + 5)
    flags = F.weak_rows(fc, 0, np.array([5, SPARE_ROW_BASE + 5]))
    np.testing.assert_array_equal(flags, [True, False])


# ---------------------------------------------------------------------------
# Zero-rate degeneracy: inactive faults are bit-identical to no faults
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("faults", [None, FaultConfig(),
                                    FaultConfig(seed=99, max_replays=1)])
def test_inactive_faults_match_arrivals_oracle(faults):
    addrs, rw, pe, arr = _trace(0)
    sched = DRAMSchedConfig(policy="frfcfs_cap", reorder_window=16,
                            starvation_cap=8, t_refi=4000, t_rfc=160)
    base = simulate_arrivals_seq(addrs, DDR4_2400, sched, rw,
                                 arrival_fpga=arr, pe_id=pe, num_ports=3)
    res = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=faults,
                              arrival_fpga=arr, pe_id=pe, num_ports=3)
    _assert_results_equal(res, base)
    assert res.attempts.max() == 1 and not res.dropped.any()
    assert res.fault.as_dict() == FaultStats().as_dict()


def test_zero_rate_pipeline_reproduces_existing_goldens():
    """The full pipeline with a zero-rate FaultConfig injected must
    reproduce the *pre-RAS* golden records exactly — every stat, stage
    count and sojourn percentile, and the schema itself (no fault
    block appears)."""
    import golden_cases

    for name in ("serving_poisson_frfcfs", "serving_hog_victim_weighted"):
        cfg, workload, apol, w = golden_cases.SERVING_CASES[name]
        assert cfg.faults is None
        stormless = dataclasses.replace(cfg, faults=FaultConfig(seed=7))
        rows, rw, pe, arr = workload()
        res = MemoryController(stormless).simulate(
            pe, rows, rw, golden_cases.ROW_BYTES, arbiter_policy=apol,
            weights=w, arrival_cycle=arr)
        assert res.fault is None and res.dropped is None
        golden_cases.SERVING_CASES[name] = (stormless, workload, apol, w)
        try:
            got = golden_cases.golden_record(name)
        finally:
            golden_cases.SERVING_CASES[name] = (cfg, workload, apol, w)
        import json
        import os
        with open(os.path.join(golden_cases.GOLDEN_DIR,
                               f"{name}.json")) as f:
            want = json.load(f)
        assert got == want


# ---------------------------------------------------------------------------
# Fast path == oracle under storms
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31), st.floats(0.0, 0.05),
       st.sampled_from(["secded", "none"]), st.booleans(),
       st.integers(0, 4), st.sampled_from([0, 16, 256]),
       st.sampled_from(["fifo", "frfcfs", "frfcfs_cap"]),
       st.booleans(), st.booleans())
@settings(max_examples=25, deadline=None)
def test_property_fast_matches_oracle_under_storm(
        seed, ber, ecc, crc, max_replays, backoff, policy, refresh,
        degrade):
    fc = FaultConfig(seed=seed, transient_ber=ber, weak_row_fraction=0.05,
                     weak_row_ber=0.4, due_fraction=0.35, ecc=ecc,
                     write_crc=crc, max_replays=max_replays,
                     backoff_clocks=backoff,
                     row_retire_threshold=2 if degrade else 0,
                     refresh_escalate_threshold=30 if degrade else 0,
                     outage_windows=((0, 1000, 2500),) if degrade else ())
    addrs, rw, pe, arr = _trace(seed % 17, n=700, ports=2)
    sched = DRAMSchedConfig(
        policy=policy, reorder_window=1 if policy == "fifo" else 16,
        starvation_cap=8, t_refi=4000 if refresh else 0, t_rfc=160)
    kw = dict(rw=rw, faults=fc, arrival_fpga=arr, pe_id=pe, num_ports=2,
              arb_policy="round_robin")
    oracle = simulate_faults_seq(addrs, DDR4_2400, sched, **kw)
    fast = simulate_faults_fast(addrs, DDR4_2400, sched, **kw)
    _assert_fault_results_equal(fast, oracle)


def test_dispatcher_engines_agree_on_storm():
    addrs, rw, pe, arr = _trace(5)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=16)
    kw = dict(rw=rw, faults=STORM, arrival_fpga=arr, pe_id=pe,
              num_ports=3, arb_policy="round_robin")
    a = simulate_faults(addrs, DDR4_2400, sched, **kw)
    b = simulate_faults_seq(addrs, DDR4_2400, sched, **kw)
    _assert_fault_results_equal(a, b)
    assert a.fault.n_injected > 0          # the storm actually landed


# ---------------------------------------------------------------------------
# Replay bounds, drops, and degradation semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("max_replays", [0, 1, 3])
def test_replay_is_bounded_and_drops_are_counted(max_replays):
    """attempts <= max_replays + 1 always; hard-failing weak cells
    (error probability 1, every error DUE/CRC) exhaust the replay
    budget at any bound, and every exhausted request is flagged
    dropped — completion stamped, never lost."""
    fc = FaultConfig(seed=1, transient_ber=0.08, due_fraction=1.0,
                     weak_row_fraction=0.1, weak_row_ber=1.0,
                     max_replays=max_replays, backoff_clocks=8)
    addrs, rw, pe, arr = _trace(2, n=800)
    res = simulate_faults_seq(addrs, DDR4_2400,
                              DRAMSchedConfig(policy="frfcfs",
                                              reorder_window=8),
                              rw, faults=fc, arrival_fpga=arr, pe_id=pe,
                              num_ports=3)
    assert int(res.attempts.max()) <= max_replays + 1
    assert res.fault.n_dropped == int(res.dropped.sum())
    assert res.fault.n_dropped > 0
    assert (res.completion_fpga_cycles > 0).all()      # nothing lost
    assert sum(res.fault.dropped_by_port.values()) == res.fault.n_dropped
    # every issue (replays included) appears in the service order
    counts = np.bincount(res.service_order, minlength=len(addrs))
    np.testing.assert_array_equal(counts, res.attempts)


def test_backoff_defers_replays():
    """With enormous backoff the replays of a failing request land
    later than with immediate retry — backoff trades the failing
    request's latency for bus time near the failure."""
    base = FaultConfig(seed=1, transient_ber=0.05, due_fraction=1.0,
                       max_replays=2, backoff_clocks=0)
    slow = dataclasses.replace(base, backoff_clocks=4096)
    addrs, rw, pe, arr = _trace(3, n=600)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=8)
    r0 = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=base,
                             arrival_fpga=arr)
    r1 = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=slow,
                             arrival_fpga=arr)
    # same storm (same seed/coords), so the same requests err...
    assert r0.fault.n_injected >= 1
    np.testing.assert_array_equal(r0.attempts >= 2, r1.attempts >= 2)
    # ...but the backed-off run finishes its victims strictly later
    errored = r0.attempts >= 2
    assert (r1.completion_fpga_cycles[errored]
            > r0.completion_fpga_cycles[errored]).all()


def test_retired_row_never_serves_again():
    """After (channel, row) appears in rows_retired, every later issue
    to that natural row serves from its spare: re-run the same trace
    with retirement disabled and confirm the retired rows keep
    erroring there, while the retire run's spare issues stop charging
    the natural row (spare_issues > 0 and the retired set is stable
    under a second pass of the same storm)."""
    fc = dataclasses.replace(STORM, row_retire_threshold=2,
                             outage_windows=())
    addrs, rw, pe, arr = _trace(7, n=2500, n_rows=150)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=16)
    res = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=fc,
                              arrival_fpga=arr)
    assert len(res.fault.rows_retired) > 0
    assert res.fault.spare_issues > 0
    retired_rows = {r for _c, r in res.fault.rows_retired}
    # a row is retired at most once — serving again would re-retire it
    assert len(retired_rows) == len(res.fault.rows_retired)
    # capacity cap respected
    assert len(retired_rows) <= fc.max_retired_rows
    capped = dataclasses.replace(fc, max_retired_rows=1)
    res1 = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=capped,
                               arrival_fpga=arr)
    assert len(res1.fault.rows_retired) <= 1


def test_refresh_escalation_fires_and_is_capped():
    fc = FaultConfig(seed=2, transient_ber=0.05,
                     refresh_escalate_threshold=10,
                     refresh_escalate_max=2)
    addrs, rw, pe, arr = _trace(8, n=2000)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=16,
                            t_refi=4000, t_rfc=160)
    res = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=fc,
                              arrival_fpga=arr)
    base = simulate_arrivals_seq(addrs, DDR4_2400, sched, rw,
                                 arrival_fpga=arr)
    assert 1 <= res.fault.refresh_escalations <= 2
    assert res.n_refreshes > base.n_refreshes   # shorter t_refi_eff


def test_outage_stalls_but_drops_nothing():
    fc = FaultConfig(seed=0, outage_windows=((0, 1000, 21000),))
    addrs, rw, pe, arr = _trace(9, n=500)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=8)
    res = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=fc,
                              arrival_fpga=arr)
    base = simulate_arrivals_seq(addrs, DDR4_2400, sched, rw,
                                 arrival_fpga=arr)
    assert res.fault.outage_dram_cycles > 0
    assert res.fault.n_dropped == 0 and not res.dropped.any()
    assert res.total_fpga_cycles > base.total_fpga_cycles
    # outage on another channel's windows is invisible to this one
    other = FaultConfig(seed=0, outage_windows=((1, 1000, 21000),))
    res2 = simulate_faults_seq(addrs, DDR4_2400, sched, rw, faults=other,
                               arrival_fpga=arr, channel=0)
    _assert_results_equal(res2, base)


# ---------------------------------------------------------------------------
# Failed channels: AddressMap bijection + pipeline remap
# ---------------------------------------------------------------------------

@given(st.sampled_from(["row_interleave", "block_interleave", "xor"]),
       st.sampled_from([(1,), (0, 2), (3,)]),
       st.integers(0, 2**31))
@settings(max_examples=20, deadline=None)
def test_property_failed_channel_map_is_bijective(policy, failed, seed):
    from repro.core.channels import AddressMap

    amap = AddressMap(ChannelConfig(num_channels=4, policy=policy),
                      DDR4_2400, FaultConfig(failed_channels=failed))
    rng = np.random.default_rng(seed)
    addrs = (rng.integers(0, 1 << 30, 400) // 64 * 64).astype(np.int64)
    ch = amap.channel_of(addrs)
    assert not np.isin(ch, list(failed)).any()
    local = amap.local_addr(addrs)
    np.testing.assert_array_equal(amap.global_addr(ch, local), addrs)


def test_pipeline_remaps_failed_channel_traffic():
    cfg = MemoryControllerConfig(
        channels=ChannelConfig(num_channels=4),
        dram_sched=DRAMSchedConfig(policy="frfcfs", reorder_window=16))
    rng = np.random.default_rng(1)
    rows = rng.integers(0, 5000, 3000)
    rw = rng.integers(0, 2, 3000).astype(np.int32)
    healthy = MemoryController(cfg).simulate(None, rows, rw, 4096)
    res = MemoryController(cfg).simulate(
        None, rows, rw, 4096,
        faults=FaultConfig(failed_channels=(2,)))
    assert res.requests_per_channel[2] == 0
    assert sum(res.requests_per_channel) == healthy.n_requests
    # served slower on 3 survivors, but everything served
    assert res.makespan_fpga_cycles > healthy.makespan_fpga_cycles
    assert res.fault is not None and res.fault.n_dropped == 0


# ---------------------------------------------------------------------------
# Pipeline / controller threading
# ---------------------------------------------------------------------------

def test_pipeline_storm_stats_and_victim_slowdown():
    """An open-loop pipeline run under the ECC storm reports the
    aggregated FaultStats block, scatters dropped flags by seq, and
    the storm slows the tenants down in aggregate (replay re-admission
    may reorder the window, so a rare individual request can finish
    earlier — the distribution, not each request, must degrade)."""
    addrs_rows = np.random.default_rng(11)
    rows = addrs_rows.integers(0, 2000, 2500)
    rw = (addrs_rows.random(2500) < 0.3).astype(np.int32)
    pe = addrs_rows.integers(0, 2, 2500)
    arr = np.cumsum(-np.log(np.clip(addrs_rows.random(2500),
                                    1e-12, 1.0)) / 0.06)
    cfg = MemoryControllerConfig(
        num_pes=2,
        dram_sched=DRAMSchedConfig(policy="frfcfs", reorder_window=16))
    clean = MemoryController(cfg).simulate(
        pe, rows, rw, 4096, arrival_cycle=arr)
    storm = MemoryController(cfg).simulate(
        pe, rows, rw, 4096, arrival_cycle=arr,
        faults=dataclasses.replace(STORM, outage_windows=()))
    assert storm.fault.n_injected > 0
    assert storm.dropped is not None
    assert int(storm.dropped.sum()) == storm.fault.n_dropped
    ok = ~storm.dropped
    slower = (storm.serving.sojourn_fpga_cycles[ok]
              >= clean.serving.sojourn_fpga_cycles[ok] - 1e-9)
    assert slower.mean() > 0.95
    assert storm.serving.mean_sojourn > clean.serving.mean_sojourn
    assert storm.serving.p99_sojourn > clean.serving.p99_sojourn


def test_simulate_rejects_empty_trace_and_bad_inputs():
    mc = MemoryController(MemoryControllerConfig())
    with pytest.raises(ValueError, match="empty trace"):
        mc.simulate(None, np.empty(0, np.int64), None, 512)
    with pytest.raises(ValueError, match="finite and >= 0"):
        mc.simulate(None, np.arange(4), None, 512,
                    arrival_cycle=np.array([0.0, 1.0, -2.0, 3.0]))
    with pytest.raises(ValueError, match="one entry per request"):
        mc.simulate(None, np.arange(4), np.zeros(3, np.int32), 512)
    with pytest.raises(ValueError, match="one entry per request"):
        mc.simulate(None, np.arange(4), None, 512,
                    arrival_cycle=np.zeros(5))
