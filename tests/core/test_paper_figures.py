"""The paper's figures and the simulator's headline findings, as exact
modeled-cycle facts.

Each case reruns one published artifact (or one acceptance finding of a
later simulator layer) through the cycle model at a fixed, seeded size
and asserts two things: the exact modeled number at that size, and the
qualitative claim beside it (the paper's, where it has one). These are
simulator outputs in FPGA cycles, not speed claims; the chip benchmark
(``bench/run.py``) is the repo's only account of speed.

Sizes: Fig. 7 runs the paper-scaled traces whole (1:1000 of the paper's
graph, the full 227x227 ResNet input layer); the pipeline, channel,
DRAM-scheduler, serving and fault cases run the golden traces'
``N_REQUESTS`` = 4000 requests (``tests/core/golden_cases.py``); the
zoo case tunes the six pinned family traces (``tests/goldens/traces``).
"""

import dataclasses
import functools

import numpy as np
import pytest

import golden_cases as gc
from repro.core import autotune, scheduler
from repro.core.autotune import sweep_serving_loads
from repro.core.cache_engine import hit_rate_oracle
from repro.core.channels import schedule_and_simulate_channels
from repro.core.config import (PAPER_COMBINED_CONFIG, PAPER_EVAL_CONFIG,
                               CacheConfig, ChannelConfig, DMAConfig,
                               DRAMSchedConfig, FaultConfig,
                               MemoryControllerConfig, SchedulerConfig,
                               scheduler_sort_stages)
from repro.core.controller import MemoryController
from repro.core.dma_engine import channel_vmem_bytes, plan_transfer
from repro.core.scheduler import READ, WRITE, schedule_trace
from repro.core.timing import (DDR4_2400, HBM_V5E, simulate_dram_access,
                               simulate_dram_access_windowed, t_schedule)
from repro.data import model_traces as mt
from repro.data.synthetic import hog_victim_workload, poisson_arrivals

VMEM_BYTES = 128 * 1024 * 1024       # v5e VMEM per chip: the "100%"
ROW_BYTES = gc.ROW_BYTES
N = gc.N_REQUESTS

BARE = MemoryControllerConfig(scheduler=SchedulerConfig(enabled=False),
                              cache=CacheConfig(enabled=False))
# FR-FCFS with a starvation cap and DDR4-2400 refresh (tRFC ~350 ns,
# tREFI ~7.8 us in command clocks): the serving and RAS service model.
SERVICE = DRAMSchedConfig(policy="frfcfs_cap", reorder_window=32,
                          starvation_cap=16, t_rfc=420, t_refi=9363)


# ---------------------------------------------------------------------------
# Fig. 7: GCN / CNN access time, controller vs commercial-IP baseline
# ---------------------------------------------------------------------------

def _fig7_gcn_trace(rng):
    """GCN inference at 1:1000 of the paper's graph: Zipf-popular
    adjacency reads (cacheable) and 4 KiB feature fetches (bulk DMA)."""
    n_vertices, n_edges = 1600, 60_000
    adj = (rng.zipf(1.2, n_edges) - 1) % n_vertices * 256
    feat = (1 << 26) + rng.integers(0, n_vertices, n_edges // 16) * 4096
    return adj, feat, 4096


def _fig7_cnn_trace():
    """ResNet input layer on 227x227 images: 7x7 windows at stride 2
    re-read overlapping image rows (cache); filter banks stream (DMA)."""
    img, k, stride = 227, 7, 2
    cache = [(y + ky) * img * 4 + x * 4
             for y in range(0, img - k, stride)
             for x in range(0, img - k, stride) for ky in range(k)]
    w_bytes = 16 * 1024
    bulk = (1 << 26) + (np.arange(220) % 8) * w_bytes
    return np.asarray(cache, np.int64), bulk, w_bytes


def _fig7_run(cache_addrs, bulk_addrs, bulk_bytes, num_pes=8):
    """Baseline: 7 PEs' bulk bursts and the cache-class stream
    interleaved round-robin into the interface, served FIFO or with a
    MIG-like 4-deep greedy reorder. Controller: the cache absorbs
    re-used lines, misses are batch-sorted by row, and bulk transfers
    stream back to back on the DMA path."""
    cfg, t = PAPER_EVAL_CONFIG, DDR4_2400
    bursts = [a + np.arange(0, bulk_bytes, 64) for a in bulk_addrs]
    streams = [np.concatenate(bursts[pe::num_pes - 1])
               for pe in range(num_pes - 1)] + [cache_addrs]
    longest = max(len(s) for s in streams)
    base = np.asarray([s[i] for i in range(longest) for s in streams
                       if i < len(s)], np.int64)
    fifo = simulate_dram_access_windowed(base, t, window=1)
    mig = simulate_dram_access_windowed(base, t, window=4)
    hits, hit_rate = hit_rate_oracle(cfg.cache,
                                     cache_addrs // cfg.cache.line_bytes)
    misses = cache_addrs[~hits]
    served = schedule_trace(misses, np.zeros(len(misses), np.int32),
                            config=cfg.scheduler, timings=t)
    cache_cycles = (simulate_dram_access(served, t).total_fpga_cycles
                    + hits.sum() + cfg.ctrl_overhead_cycles)
    dma_cycles = simulate_dram_access(np.concatenate(bursts),
                                      t).total_fpga_cycles
    ctrl = cache_cycles + dma_cycles
    return {"vs_mig": round(1 - ctrl / mig.total_fpga_cycles, 4),
            "vs_fifo": round(1 - ctrl / fifo.total_fpga_cycles, 4),
            "cache_hit": round(float(hit_rate), 4),
            "dma_share": round(dma_cycles / ctrl, 4)}


def fig7_gcn():
    got = _fig7_run(*_fig7_gcn_trace(np.random.default_rng(0)))
    assert got == {"vs_mig": 0.2895, "vs_fifo": 0.4356,
                   "cache_hit": 0.8791, "dma_share": 0.9131}
    # paper: GCN access time -27%, DMA-dominant
    assert got["vs_mig"] > 0 and got["dma_share"] > 0.5


def fig7_cnn():
    got = _fig7_run(*_fig7_cnn_trace())
    assert got == {"vs_mig": 0.4755, "vs_fifo": 0.5082,
                   "cache_hit": 0.9623, "dma_share": 0.7503}
    # paper: CNN access time -58%, the larger of the two wins
    assert got["vs_mig"] > 0.2895


# ---------------------------------------------------------------------------
# Fig. 7, write extension: scheduling on vs off on write-heavy streams
# ---------------------------------------------------------------------------

def _embedding_grad_trace(rng, vocab=50_000, n_tokens=20_000,
                          row_bytes=4096, num_pes=8):
    """Read-modify-write per token over a Zipf vocabulary, from 8
    workers whose in-order streams interleave at random."""
    addrs = (rng.zipf(1.3, n_tokens) - 1) % vocab * row_bytes
    per_a = [np.repeat(addrs[p::num_pes], 2) for p in range(num_pes)]
    per_rw = [np.tile(np.array([READ, WRITE], np.int32), len(a) // 2)
              for a in per_a]
    keys = np.concatenate([np.sort(rng.random(len(a))) for a in per_a])
    order = np.argsort(keys, kind="stable")
    return (np.concatenate(per_a)[order].astype(np.int64),
            np.concatenate(per_rw)[order])


def _kv_append_trace(rng, batch=32, steps=256, page_bytes=2048):
    """32 decoding sequences each append one KV page a step, after
    reading up to four random earlier pages (attention)."""
    addrs, rw = [], []
    for t in range(steps):
        for b in range(batch):
            for p in (rng.integers(0, t, min(4, t)) if t else ()):
                addrs.append((b << 24) + p * page_bytes)
                rw.append(READ)
            addrs.append((b << 24) + t * page_bytes)
            rw.append(WRITE)
    return np.asarray(addrs, np.int64), np.asarray(rw, np.int32)


@functools.cache
def _fig7_write():
    rng = np.random.default_rng(0)
    out = {}
    for name, (addrs, rw) in (("embedding_grad", _embedding_grad_trace(rng)),
                              ("kv_append", _kv_append_trace(rng))):
        off = simulate_dram_access(addrs, DDR4_2400, rw=rw)
        served, served_rw = scheduler.schedule_trace_rw(
            addrs, rw, config=PAPER_EVAL_CONFIG.scheduler,
            timings=DDR4_2400, coalesce_writes=True)
        on = simulate_dram_access(served, DDR4_2400, rw=served_rw)
        out[name] = {
            "on_off": (round(on.total_fpga_cycles),
                       round(off.total_fpga_cycles)),
            "coalesced": len(addrs) - len(served),
            "turnarounds": (int((rw[1:] != rw[:-1]).sum()),
                            int((served_rw[1:] != served_rw[:-1]).sum())),
        }
    return out


def fig7_write_embedding_grad():
    got = _fig7_write()["embedding_grad"]
    assert got == {"on_off": (310220, 336925), "coalesced": 6194,
                   "turnarounds": (22459, 625)}     # 7.93% faster


def fig7_write_kv_append():
    got = _fig7_write()["kv_append"]
    assert got == {"on_off": (500355, 553126), "coalesced": 0,
                   "turnarounds": (16320, 254)}     # 9.54% faster


# ---------------------------------------------------------------------------
# Fig. 8: 16 KiB sequential access, cache-line path vs one DMA descriptor
# ---------------------------------------------------------------------------

def fig8_dma_advantage():
    cfg, t, total = PAPER_EVAL_CONFIG, DDR4_2400, 16 * 1024
    line = cfg.cache.line_bytes
    dma = (simulate_dram_access(np.arange(0, total, t.burst_bytes),
                                t).total_fpga_cycles
           + cfg.ctrl_overhead_cycles + 2)
    misses = simulate_dram_access(np.arange(total // line) * line,
                                  t).total_fpga_cycles
    cache = {w: misses + (total // w - total // line)
             + cfg.ctrl_overhead_cycles + 4 for w in (1, 2, 4, 8, 16, 32, 64)}
    assert round(dma) == 1364
    assert {w: round(c) for w, c in cache.items()} == {
        1: 17494, 2: 9302, 4: 5206, 8: 3158, 16: 2134, 32: 1622, 64: 1366}
    # paper: ~20x for DMA at the narrowest interface, shrinking with width
    ratios = [cache[w] / dma for w in sorted(cache)]
    assert round(ratios[0], 2) == 12.82
    assert all(a > b for a, b in zip(ratios, ratios[1:])) and ratios[-1] >= 1


# ---------------------------------------------------------------------------
# Fig. 9: schedule time vs batch size, the 32-64 optimum
# ---------------------------------------------------------------------------

def fig9_optimum():
    """Total time = first batch's Eq. 1 formation + DRAM service of the
    row-sorted stream + scheduling not hidden behind service. The
    paper picks the best performance under modest resources: saving
    per LUT, with LUT/FF ~3x per batch doubling (Fig. 6, ~N^1.585)."""
    n, rows = 8192, 48
    addrs = np.random.default_rng(0).integers(0, rows, n) * DDR4_2400.row_bytes
    base = simulate_dram_access(addrs).total_fpga_cycles
    total, per_lut = {}, {}
    for batch in (4, 8, 16, 32, 64, 128, 256, 512):
        served = schedule_trace(
            addrs, np.zeros(n, np.int32),
            config=SchedulerConfig(batch_size=batch,
                                   bypass_sequential=False))
        dram = simulate_dram_access(served).total_fpga_cycles
        n_batches = n // batch
        resid = max(0.0, t_schedule(batch) - dram / n_batches) \
            * (n_batches - 1)
        total[batch] = round(t_schedule(batch) + dram + resid)
        per_lut[batch] = (base - total[batch]) / batch ** 1.585
    assert total == {4: 89817, 8: 89713, 16: 88926, 32: 86602, 64: 79519,
                     128: 67303, 256: 56214, 512: 50012}
    assert max(per_lut, key=per_lut.get) == 64        # paper: 32-64


# ---------------------------------------------------------------------------
# Table III, Fig. 5, Fig. 6: resource and cost models
# ---------------------------------------------------------------------------

def table3_cache_resources():
    """Cache storage grows linearly in line width x line count; on a
    TPU it is a share of VMEM (the FPGA's URAM/BRAM %)."""
    share = {(w, ways, lines): round(100 * CacheConfig(
        line_width_bits=w, num_lines=lines,
        associativity=ways).capacity_bytes / VMEM_BYTES, 4)
        for w, ways, lines in ((512, 1, 512), (512, 2, 8192),
                               (4096, 2, 8192), (512, 8, 32768))}
    assert share == {(512, 1, 512): 0.0244, (512, 2, 8192): 0.3906,
                     (4096, 2, 8192): 3.125, (512, 8, 32768): 1.5625}


def fig5_dma_resources():
    """Staging VMEM climbs linearly with simultaneous DMAs x buffer
    size; a 1 MiB copy splits into buffer-sized transactions."""
    got = {}
    for kb, ch in ((4, 1), (16, 4), (64, 8)):
        cfg = DMAConfig(buffer_bytes=kb * 1024, num_parallel_dma=ch,
                        max_transaction_bytes=kb * 1024)
        got[(kb, ch)] = (channel_vmem_bytes(cfg),
                         plan_transfer(1 << 20, cfg).num_transactions)
    assert got == {(4, 1): (8192, 256), (16, 4): (131072, 64),
                   (64, 8): (1048576, 16)}


def fig6_scheduler_cost():
    """Eq. 1: N cycles of formation + log2(N)(log2(N)+1)/2 bitonic
    stages + 2 cycles of data conditioning."""
    got = {n: (scheduler_sort_stages(n), t_schedule(n))
           for n in (4, 32, 64, 512)}
    assert got == {4: (3, 9.0), 32: (15, 49.0), 64: (21, 87.0),
                   512: (45, 559.0)}


# ---------------------------------------------------------------------------
# Fig. 7 composed: the combined configuration through the staged pipeline
# ---------------------------------------------------------------------------

def _makespans(trace):
    rows, rw = trace
    configs = {
        "scheduler_only_1ch": MemoryControllerConfig(
            cache=CacheConfig(enabled=False)),
        "scheduler_only_4ch": MemoryControllerConfig(
            cache=CacheConfig(enabled=False),
            channels=ChannelConfig(num_channels=4)),
        "combined": PAPER_COMBINED_CONFIG,
    }
    return {k: MemoryController(c).simulate(None, rows, rw,
                                            ROW_BYTES).makespan_fpga_cycles
            for k, c in configs.items()}


def pipeline_combined_gcn():
    got = _makespans(gc.gcn_trace())
    assert {k: round(v, 3) for k, v in got.items()} == {
        "scheduler_only_1ch": 42542.261, "scheduler_only_4ch": 11841.975,
        "combined": 8817.803}
    assert got["combined"] < min(got["scheduler_only_1ch"],
                                 got["scheduler_only_4ch"])


def pipeline_combined_cnn():
    got = _makespans(gc.cnn_trace())
    assert {k: round(v, 3) for k, v in got.items()} == {
        "scheduler_only_1ch": 28193.068, "scheduler_only_4ch": 7228.11,
        "combined": 6458.495}
    assert got["combined"] < min(got["scheduler_only_1ch"],
                                 got["scheduler_only_4ch"])


# ---------------------------------------------------------------------------
# Channels and the DRAM command scheduler
# ---------------------------------------------------------------------------

def channels_monotonic_gcn():
    rows, rw = gc.gcn_trace()
    got = {}
    for name, t in (("DDR4_2400", DDR4_2400), ("HBM_V5E", HBM_V5E)):
        got[name] = [round(schedule_and_simulate_channels(
            rows * ROW_BYTES, rw, sched_config=SchedulerConfig(batch_size=64),
            timings=t, channel_cfg=ChannelConfig(num_channels=c)
        ).makespan_fpga_cycles, 3) for c in (1, 2, 4)]
    assert got == {"DDR4_2400": [42445.261, 21530.788, 11744.975],
                   "HBM_V5E": [60157.801, 30609.774, 17521.429]}
    for curve in got.values():
        assert curve[0] > curve[1] > curve[2]


def dram_sched_window_sweep_gcn():
    """FR-FCFS at window >= 8 beats FIFO service on the GCN trace, and
    refresh taxes the best window by a few percent."""
    rows, rw = gc.gcn_trace()

    def makespan(**kw):
        cfg = dataclasses.replace(BARE, dram_sched=DRAMSchedConfig(**kw))
        return MemoryController(cfg).simulate(
            None, rows, rw, ROW_BYTES).makespan_fpga_cycles

    fifo = makespan()
    speedup = {w: round(fifo / makespan(policy="frfcfs", reorder_window=w),
                        4) for w in (8, 16, 32, 64)}
    assert speedup == {8: 1.0252, 16: 1.0412, 32: 1.0718, 64: 1.1129}
    assert min(speedup.values()) > 1
    tax = makespan(policy="frfcfs", reorder_window=64, t_rfc=420,
                   t_refi=9363) / makespan(policy="frfcfs",
                                           reorder_window=64)
    assert round(tax, 4) == 1.0189


# ---------------------------------------------------------------------------
# Open-loop serving: the offered-load sweep to saturation
# ---------------------------------------------------------------------------

def serving_load_sweep():
    rows, rw = gc.gcn_trace()
    cfg = dataclasses.replace(BARE, dram_sched=SERVICE)
    closed = MemoryController(cfg).simulate(None, rows, rw, ROW_BYTES)
    capacity = N / closed.makespan_fpga_cycles
    fracs = (0.2, 0.5, 0.8, 0.95, 1.1, 1.4)
    arrivals = [poisson_arrivals(np.random.default_rng(17), N,
                                 capacity * f) for f in fracs]
    runs = sweep_serving_loads(cfg, rows, rw, None, arrivals, ROW_BYTES)
    p99 = {f: round(r.serving.p99_sojourn, 1) for f, r in zip(fracs, runs)}
    assert p99 == {0.2: 110.5, 0.5: 141.5, 0.8: 194.7, 0.95: 479.0,
                   1.1: 4478.0, 1.4: 12602.7}
    # the knee: the first load whose p99 passes 3x the light-load p99
    assert next(f for f in fracs if p99[f] > 3 * p99[0.2]) == 0.95
    # past saturation the tail blows up and the rate pins at capacity
    assert p99[1.4] > 10 * p99[0.2]
    sustained = runs[-1].serving.sustained_req_per_cycle
    assert abs(sustained - capacity) < 0.05 * capacity


# ---------------------------------------------------------------------------
# RAS: retry policies under an error storm, and a channel outage
# ---------------------------------------------------------------------------

@functools.cache
def _fault_storm():
    n_victim = N // 5
    cfg = dataclasses.replace(BARE, dram_sched=SERVICE, num_pes=2)

    def simulate(pe, rows, rw, arr, faults=None):
        return MemoryController(cfg).simulate(
            pe, rows, rw, ROW_BYTES, arbiter_policy="weighted",
            weights=(4, 1), arrival_cycle=arr, faults=faults)

    rows, rw, pe, _ = hog_victim_workload(
        np.random.default_rng(4), n_victim=n_victim, n_hog=N - n_victim,
        victim_rate=1.0, hog_rate=1.0)
    capacity = N / simulate(pe, rows, rw, None).makespan_fpga_cycles
    rows, rw, pe, arr = hog_victim_workload(
        np.random.default_rng(4), n_victim=n_victim, n_hog=N - n_victim,
        victim_rate=0.15 * capacity, hog_rate=0.75 * capacity)
    # transient errors everywhere plus hard-failed weak cells
    storm = FaultConfig(seed=9, transient_ber=0.02, weak_row_fraction=0.02,
                        weak_row_ber=1.0, due_fraction=1.0)
    policies = {
        "bounded_backoff": dict(max_replays=4, backoff_clocks=64),
        "naive_retry": dict(max_replays=16, backoff_clocks=0),
        "no_ecc": dict(ecc="none", write_crc=False),
    }
    out = {"clean": simulate(pe, rows, rw, arr)}
    for name, knobs in policies.items():
        out[name] = simulate(pe, rows, rw, arr,
                             dataclasses.replace(storm, **knobs))
    span = float(arr.max())
    out["outage"] = simulate(pe, rows, rw, arr, FaultConfig(
        seed=9, outage_windows=((0, int(0.2 * span), int(0.45 * span)),)))
    return out


def _victim_p99(res):
    return round(res.serving.per_port[0]["p99_sojourn"], 1)


def faults_bounded_beats_naive():
    runs = _fault_storm()
    got = {k: _victim_p99(runs[k])
           for k in ("clean", "bounded_backoff", "naive_retry")}
    assert got == {"clean": 478.2, "bounded_backoff": 513.9,
                   "naive_retry": 774.8}
    assert got["bounded_backoff"] < got["naive_retry"]


def faults_no_ecc_fast_but_corrupts():
    runs = _fault_storm()
    assert _victim_p99(runs["no_ecc"]) == _victim_p99(runs["clean"])
    assert (runs["no_ecc"].fault.n_silent,
            runs["bounded_backoff"].fault.n_silent) == (135, 0)


def faults_outage_slower_zero_drops():
    runs = _fault_storm()
    clean, out = runs["clean"], runs["outage"]
    assert (round(clean.serving.p99_sojourn, 1),
            round(out.serving.p99_sojourn, 1)) == (1460.6, 3046.4)
    assert out.makespan_fpga_cycles >= clean.makespan_fpga_cycles
    assert out.fault.n_dropped == 0


# ---------------------------------------------------------------------------
# §V's tuning question on captured model traffic
# ---------------------------------------------------------------------------

def zoo_geometry_differs_across_families():
    """The batched tune over the joint cache x channels x mapping x
    scheduler x DRAM-policy grid picks a different geometry for
    different model families, as the paper's GCN differs from CNN."""
    grid = dict(batch_sizes=(16, 64, 256), associativities=(1, 4),
                num_lines=(1024, 4096, 16384), dma_channels=(4,),
                num_channels=(1, 2, 4),
                mapping_policies=("row_interleave", "xor"),
                dram_sched_policies=("fifo", "frfcfs"),
                reorder_windows=(1, 16, 64))
    got = {}
    for fam, arch in sorted(mt.FAMILY_REPRESENTATIVE.items()):
        _, rows, _ = mt.load_pinned_trace(arch).replay_arrays(
            PAPER_COMBINED_CONFIG.num_pes)
        c = autotune.tune(rows, mt.REPLAY_ROW_BYTES, **grid).config
        got[fam] = (c.cache.num_lines, c.channels.policy,
                    c.dram_sched.policy, c.dram_sched.reorder_window)
    assert got == {
        "dense": (1024, "xor", "frfcfs", 16),
        "encoder": (1024, "row_interleave", "fifo", 1),
        "hybrid": (16384, "row_interleave", "frfcfs", 16),
        "moe": (16384, "row_interleave", "frfcfs", 16),
        "ssm": (1024, "xor", "frfcfs", 64),
        "vlm": (4096, "xor", "frfcfs", 64),
    }
    assert len(set(got.values())) >= 2


FACTS = {f.__name__: f for f in (
    fig7_gcn, fig7_cnn, fig7_write_embedding_grad, fig7_write_kv_append,
    fig8_dma_advantage, fig9_optimum, table3_cache_resources,
    fig5_dma_resources, fig6_scheduler_cost, pipeline_combined_gcn,
    pipeline_combined_cnn, channels_monotonic_gcn,
    dram_sched_window_sweep_gcn, serving_load_sweep,
    faults_bounded_beats_naive, faults_no_ecc_fast_but_corrupts,
    faults_outage_slower_zero_drops, zoo_geometry_differs_across_families)}


@pytest.mark.parametrize("fact", sorted(FACTS))
def test_paper_figure(fact):
    FACTS[fact]()
