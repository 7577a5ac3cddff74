"""autotune.tune — grid membership, improvement over the seed config, and
the new channel/mapping search axes."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.autotune import _score, sweep_serving_loads, tune, tune_seq
from repro.core.config import (CacheConfig, DRAMSchedConfig,
                               MemoryControllerConfig, SchedulerConfig)
from repro.core.controller import MemoryController
from repro.core.timing import DDR4_2400


@pytest.fixture
def trace(rng):
    # Zipf-hot rows: cacheable head, irregular tail — the regime where
    # batch size, cache shape and channel count all matter.
    return ((rng.zipf(1.3, 4096) - 1) % 2048).astype(np.int64)


def test_tune_returns_config_from_the_searched_grid(trace):
    grids = dict(batch_sizes=(16, 64), associativities=(1, 4),
                 num_lines=(1024, 4096), dma_channels=(1, 4),
                 num_channels=(1, 4),
                 mapping_policies=("row_interleave", "xor"))
    res = tune(trace, 512, **grids)
    cfg = res.config
    assert cfg.scheduler.batch_size in grids["batch_sizes"]
    assert cfg.cache.associativity in grids["associativities"]
    assert cfg.cache.num_lines in grids["num_lines"]
    assert cfg.dma.num_parallel_dma in grids["dma_channels"]
    assert cfg.channels.num_channels in grids["num_channels"]
    assert cfg.channels.policy in grids["mapping_policies"]
    # every feasible grid point was scored and the winner is the argmin
    assert res.candidates_evaluated == len(res.table)
    assert res.modeled_cycles == min(c for _, c in res.table)


def test_tune_beats_seed_config_on_fixed_trace(trace):
    """The tuned config's modeled score must be no worse than the
    Table-I default configuration scored on the same trace (the seed is
    in the search space, so the grid argmin can only improve on it)."""
    seed_cfg = MemoryControllerConfig()
    seed_cycles = _score(seed_cfg, trace, 512, timings=DDR4_2400)
    res = tune(trace, 512,
               batch_sizes=(seed_cfg.scheduler.batch_size, 128),
               associativities=(seed_cfg.cache.associativity,),
               num_lines=(seed_cfg.cache.num_lines,),
               dma_channels=(seed_cfg.dma.num_parallel_dma,),
               num_channels=(1, 2, 4))
    assert res.modeled_cycles <= seed_cycles
    # the channel axis is genuinely helping on an irregular trace: the
    # best multi-channel candidate beats every single-channel candidate
    best_multi = min(c for d, c in res.table if "mem_ch=4" in d)
    best_single = min(c for d, c in res.table if "mem_ch=1" in d)
    assert best_multi < best_single


def test_tune_exercises_channel_and_mapping_axes(trace):
    res = tune(trace, 512, batch_sizes=(64,), associativities=(4,),
               num_lines=(4096,), dma_channels=(4,),
               num_channels=(1, 2), mapping_policies=("row_interleave",
                                                      "block_interleave",
                                                      "xor"))
    descs = [d for d, _ in res.table]
    # one channel collapses the policy axis (identity map); two channels
    # score every policy
    assert sum("mem_ch=1" in d for d in descs) == 1
    assert sum("mem_ch=2" in d for d in descs) == 3
    assert {d.split("map=")[1].split()[0] for d in descs
            if "mem_ch=2" in d} == \
        {"row_interleave", "block_interleave", "xor"}


def test_tune_channel_axis_respects_vmem_budget(trace):
    """Per-channel scheduler queues multiply the footprint: a budget that
    fits one channel's queues but not eight must prune the 8-channel
    candidates rather than crash."""
    budget = 600 << 10          # fits 1-channel queues (~392KiB), not 8
    res = tune(trace, 512, vmem_budget_bytes=budget,
               batch_sizes=(512,), associativities=(4,),
               num_lines=(4096,), dma_channels=(1,),
               num_channels=(1, 8))
    assert res.config.vmem_footprint_bytes() <= budget
    assert all("mem_ch=8" not in d for d, _ in res.table)


def test_tune_exercises_dram_sched_axes(trace):
    """dram_sched_policies x reorder_windows join the grid; the
    FIFO/window-1 collapse is deduplicated (fifo is scored once, not
    once per window), and the winner carries a config from the grid."""
    res = tune(trace, 512, batch_sizes=(64,), associativities=(4,),
               num_lines=(4096,), dma_channels=(4,),
               dram_sched_policies=("fifo", "frfcfs"),
               reorder_windows=(1, 8, 32))
    descs = [d for d, _ in res.table]
    assert sum("dsched=fifo:1" in d for d in descs) == 1
    assert sum("dsched=frfcfs:8" in d for d in descs) == 1
    assert sum("dsched=frfcfs:32" in d for d in descs) == 1
    assert len(descs) == 3
    assert res.config.dram_sched.policy in ("fifo", "frfcfs")
    assert res.config.dram_sched.reorder_window in (1, 8, 32)
    # on a zipf-hot trace with the cache absorbing the head, deeper
    # reorder windows can only help the modeled DRAM service — the
    # frfcfs candidates must not lose to fifo
    best_fr = min(c for d, c in res.table if "frfcfs" in d)
    fifo_c = next(c for d, c in res.table if "dsched=fifo:1" in d)
    assert best_fr <= fifo_c


def test_tune_default_grid_unchanged(trace):
    """The default axes keep the pre-PR search space: every candidate
    is scored with the FIFO window-1 service model."""
    res = tune(trace, 512, batch_sizes=(16,), associativities=(4,),
               num_lines=(1024,), dma_channels=(1,))
    assert all("dsched=fifo:1" in d for d, _ in res.table)
    assert res.config.dram_sched == \
        MemoryControllerConfig().dram_sched


def test_tune_serving_constrained_selection():
    """tune_serving searches arbiter x scheduler QoS knobs: the winner
    comes from the grid, every candidate is tabulated, and a feasible
    SLO target flips the objective from p99-min to makespan-min among
    feasible candidates."""
    from repro.core.autotune import tune_serving
    from repro.data.synthetic import hog_victim_workload

    rows, rw, pe, arr = hog_victim_workload(
        np.random.default_rng(7), n_victim=200, n_hog=800,
        victim_rate=0.02, hog_rate=0.2)
    res = tune_serving(rows, rw, pe, arr, 4096, num_ports=2,
                       arb_policies=("round_robin", "weighted"),
                       weight_ratios=(4,),
                       dram_sched_policies=("frfcfs", "frfcfs_cap"),
                       reorder_windows=(16,), starvation_caps=(8,))
    # 2 arb x 2 sched candidates, all tabulated
    assert res.candidates_evaluated == len(res.table) == 4
    assert res.arb_policy in ("round_robin", "weighted")
    assert res.config.dram_sched.policy in ("frfcfs", "frfcfs_cap")
    assert res.slo_p99_cycles > 0 and res.makespan_cycles > 0
    # no target: objective is the SLO port's p99 outright
    assert not res.feasible
    assert res.slo_p99_cycles == min(p for _, p, _ in res.table)
    # a generous target makes every candidate feasible -> makespan-min
    res2 = tune_serving(rows, rw, pe, arr, 4096, num_ports=2,
                        slo_p99_cycles=1e12,
                        arb_policies=("round_robin", "weighted"),
                        weight_ratios=(4,),
                        dram_sched_policies=("frfcfs", "frfcfs_cap"),
                        reorder_windows=(16,), starvation_caps=(8,))
    assert res2.feasible
    assert res2.makespan_cycles == min(m for _, _, m in res2.table)


# ---------------------------------------------------------------------------
# Batched grid scorer == one-at-a-time oracle (ISSUE 9 tentpole c)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.sampled_from([(1.05, 1 << 14, 64), (1.3, 2048, 512),
                        (1.2, 256, 4096)]),
       st.sampled_from([((16, 64), (1, 4), (1024, 4096)),
                        ((8,), (2,), (256, 16384))]),
       st.sampled_from([((1,), ("row_interleave",)),
                        ((1, 2, 4), ("row_interleave", "xor")),
                        ((2,), ("block_interleave",))]),
       st.sampled_from([(("fifo",), (1,)),
                        (("fifo", "frfcfs"), (1, 8)),
                        (("frfcfs", "frfcfs_cap"), (4, 32))]),
       st.booleans(),
       st.integers(0, 5))
def test_property_batched_tune_matches_oracle(workload, cache_axes,
                                              chan_axes, sched_axes,
                                              enable_cache, seed):
    """tune (the batched scorer) must reproduce tune_seq bit
    for bit — every table entry, the argmin config, the modeled score
    and the candidate count — across cache/channel/sched grids, skew
    levels and cache-off runs."""
    skew, n_rows, row_bytes = workload
    batches, ways, lines = cache_axes
    n_chans, mappings = chan_axes
    spols, wins = sched_axes
    rng = np.random.default_rng(seed)
    rows = ((rng.zipf(skew, 1500) - 1) % n_rows).astype(np.int64)
    grids = dict(batch_sizes=batches, associativities=ways,
                 num_lines=lines, dma_channels=(1, 4),
                 num_channels=n_chans, mapping_policies=mappings,
                 dram_sched_policies=spols, reorder_windows=wins,
                 enable_cache=enable_cache)
    a = tune_seq(rows, row_bytes, **grids)
    b = tune(rows, row_bytes, **grids)
    assert a.table == b.table
    assert a.config == b.config
    assert a.modeled_cycles == b.modeled_cycles
    assert a.candidates_evaluated == b.candidates_evaluated


def test_batched_tune_tiny_and_degenerate_traces():
    """Five-request and single-request traces: the vectorized batch
    plan and fused-key classification must survive the degenerate
    shapes (partial batches everywhere, empty channels after
    splitting)."""
    for rows in (np.asarray([7], np.int64),
                 np.asarray([3, 3, 9, 3, 11], np.int64)):
        a = tune_seq(rows, 4096,
                 batch_sizes=(4, 64), associativities=(1,),
                 num_lines=(1024,), dma_channels=(1,),
                 num_channels=(1, 4),
                 dram_sched_policies=("fifo", "frfcfs"),
                 reorder_windows=(1, 8))
        b = tune(rows, 4096,
                 batch_sizes=(4, 64), associativities=(1,),
                 num_lines=(1024,), dma_channels=(1,),
                 num_channels=(1, 4),
                 dram_sched_policies=("fifo", "frfcfs"),
                 reorder_windows=(1, 8))
        assert a.table == b.table and a.config == b.config


# ---------------------------------------------------------------------------
# sweep_serving_loads == MemoryController.simulate per point
# ---------------------------------------------------------------------------

def test_sweep_serving_loads_matches_controller(rng):
    n = 3000
    rows = ((rng.zipf(1.2, n) - 1) % 4096).astype(np.int64)
    rw = (rng.random(n) < 0.2).astype(np.int32)
    cfg = MemoryControllerConfig(
        scheduler=SchedulerConfig(enabled=False),
        cache=CacheConfig(enabled=False),
        dram_sched=DRAMSchedConfig(policy="frfcfs_cap",
                                   reorder_window=16, starvation_cap=8,
                                   t_rfc=420, t_refi=9363))
    cap = 0.09
    arrivals = [np.cumsum(rng.exponential(1.0 / (cap * f), n))
                for f in (0.5, 1.2)]
    swept = sweep_serving_loads(cfg, rows, rw, None, arrivals, 4096)
    mc = MemoryController(cfg)
    for arr, res in zip(arrivals, swept):
        ref = mc.simulate(None, rows, rw, 4096, arrival_cycle=arr)
        assert ref.makespan_fpga_cycles == res.makespan_fpga_cycles
        assert ref.serving.p50_sojourn == res.serving.p50_sojourn
        assert ref.serving.p99_sojourn == res.serving.p99_sojourn
        assert (ref.serving.sustained_req_per_cycle
                == res.serving.sustained_req_per_cycle)
        np.testing.assert_array_equal(ref.serving.sojourn_fpga_cycles,
                                      res.serving.sojourn_fpga_cycles)


def test_sweep_serving_loads_multiport_weighted(rng):
    n = 2000
    rows = ((rng.zipf(1.3, n) - 1) % 2048).astype(np.int64)
    pe = rng.integers(0, 2, n).astype(np.int32)
    arr = np.cumsum(rng.exponential(8.0, n))
    cfg = MemoryControllerConfig(
        num_pes=2,
        scheduler=SchedulerConfig(enabled=False),
        cache=CacheConfig(enabled=False),
        dram_sched=DRAMSchedConfig(policy="frfcfs", reorder_window=8))
    swept = sweep_serving_loads(cfg, rows, None, pe, [arr], 4096,
                                arbiter_policy="weighted",
                                weights=(4, 1))
    ref = MemoryController(cfg).simulate(
        pe, rows, None, 4096,
        arbiter_policy="weighted", weights=(4, 1), arrival_cycle=arr)
    res = swept[0]
    assert ref.makespan_fpga_cycles == res.makespan_fpga_cycles
    for p in ("0", "1"):
        assert (ref.serving.per_port[int(p)]["p99_sojourn"]
                == res.serving.per_port[int(p)]["p99_sojourn"])


def test_sweep_serving_loads_validates_arrivals(rng):
    rows = np.arange(64, dtype=np.int64)
    cfg = MemoryControllerConfig(
        scheduler=SchedulerConfig(enabled=False),
        cache=CacheConfig(enabled=False))
    with pytest.raises(ValueError, match="one entry per request"):
        sweep_serving_loads(cfg, rows, None, None,
                            [np.zeros(3)], 4096)
    with pytest.raises(ValueError, match="finite"):
        sweep_serving_loads(cfg, rows, None, None,
                            [np.full(64, np.nan)], 4096)
