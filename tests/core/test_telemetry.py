"""Per-request lifecycle tracing (repro.core.telemetry) — the event
stream, attribution and export contracts of ARCHITECTURE §11.

Four properties lock the subsystem down:

1. **Reconstruction fidelity** — the fast paths' replayed event
   streams are *event-for-event equal* (same tuples, same order) to
   what the seq oracles emit natively, across the serving ×
   dram_sched × faults grid. The oracle stream IS the spec; the fast
   path must not invent or lose a single event.
2. **Tracing is free when off and invisible when on** — ``trace=None``
   changes nothing (it's the default everywhere), and passing a
   recorder must leave every modeled number bit-identical to the
   untraced run (golden-pinned cases included).
3. **The attribution identity** — the nine per-request components sum
   *exactly* (left-to-right, bit-for-bit) to the run's sojourns.
4. **The export contract** — the Chrome-trace JSON validates against
   the structural schema the CI trace-smoke step enforces.
"""

import dataclasses
import json

import numpy as np
import pytest

from golden_cases import CASES, ROW_BYTES, SERVING_CASES
from repro.core import timing
from repro.core.config import (DRAMSchedConfig, FaultConfig,
                               MemoryControllerConfig)
from repro.core.controller import MemoryController
from repro.core.telemetry import (COMPONENTS, ChannelTrace,
                                  CycleAttribution, TraceRecorder)
from repro.launch import tracing


def _norm(events):
    """Plain-python view of an event list (numpy scalars stripped) so
    equality failures render readably."""
    return [tuple(float(x) if isinstance(x, (float, np.floating))
                  else int(x) if isinstance(x, (int, np.integer))
                  else x for x in e) for e in events]


def _trace_pair(fast_fn, seq_fn, *args, **kwargs):
    """Run the fast engine and its ``*_seq`` oracle, each under a fresh
    ChannelTrace; assert results agree and return both event lists."""
    seq_t, fast_t = ChannelTrace(), ChannelTrace()
    seq = seq_fn(*args, trace=seq_t, **kwargs)
    fast = fast_fn(*args, trace=fast_t, **kwargs)
    assert seq.total_fpga_cycles == fast.total_fpga_cycles
    return _norm(seq_t.events), _norm(fast_t.events)


def _addrs(rng, n, n_rows=256):
    rows = np.minimum((1.0 / np.clip(rng.random(n), 1e-9, 1.0)) ** 0.8,
                      n_rows - 1).astype(np.int64)
    return rows * timing.DDR4_2400.row_bytes


# ---------------------------------------------------------------------------
# 1. reconstruction fidelity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy,window,cap,t_rfc,t_refi", [
    ("fifo", 1, 16, 0, 0),
    ("fifo", 1, 16, 420, 9363),         # refresh on the FIFO walk
    ("frfcfs", 16, 16, 0, 0),
    ("frfcfs", 16, 16, 420, 9363),
    ("frfcfs_cap", 32, 8, 420, 9363),
])
def test_sched_events_fast_equals_oracle(policy, window, cap, t_rfc,
                                         t_refi):
    rng = np.random.default_rng(17)
    addrs = _addrs(rng, 1500)
    rw = (rng.random(1500) < 0.3).astype(np.int32)
    sched = DRAMSchedConfig(policy=policy, reorder_window=window,
                            starvation_cap=cap, t_rfc=t_rfc,
                            t_refi=t_refi)
    seq_ev, fast_ev = _trace_pair(timing.simulate_dram_sched,
                                  timing.simulate_dram_sched_seq, addrs,
                                  timing.DDR4_2400, sched, rw)
    assert seq_ev == fast_ev
    assert any(e[0] == "issue" for e in seq_ev)
    if t_refi:
        assert any(e[0] == "refresh" for e in seq_ev)


@pytest.mark.parametrize("num_ports,arb,weights,rate", [
    (None, "round_robin", None, 0.05),
    (1, "round_robin", None, 0.02),
    (3, "round_robin", None, 0.05),
    (3, "weighted", (4, 1, 1), 0.05),
    (3, "priority", None, 0.08),
])
def test_arrival_events_fast_equals_oracle(num_ports, arb, weights,
                                           rate):
    rng = np.random.default_rng(23)
    n = 1200
    addrs = _addrs(rng, n)
    rw = (rng.random(n) < 0.2).astype(np.int32)
    arr = np.cumsum(rng.exponential(1.0 / rate, n))
    pe = None if num_ports is None \
        else rng.integers(0, num_ports, n)
    sched = DRAMSchedConfig(policy="frfcfs", reorder_window=16,
                            t_rfc=420, t_refi=9363)
    seq_ev, fast_ev = _trace_pair(
        timing.simulate_arrivals, timing.simulate_arrivals_seq, addrs,
        timing.DDR4_2400, sched, rw,
        arrival_fpga=arr, pe_id=pe, num_ports=num_ports,
        arb_policy=arb, weights=weights)
    assert seq_ev == fast_ev
    kinds = {e[0] for e in seq_ev}
    assert {"grant", "issue", "complete"} <= kinds


@pytest.mark.parametrize("fc", [
    FaultConfig(seed=11, transient_ber=0.004, weak_row_fraction=0.02,
                weak_row_ber=0.5, due_fraction=0.25, max_replays=4,
                backoff_clocks=32, row_retire_threshold=2,
                refresh_escalate_threshold=40),
    FaultConfig(seed=5, outage_windows=((0, 4000, 9000),)),
    FaultConfig(seed=3),                # inactive: fault-free stream
])
def test_fault_events_fast_equals_oracle(fc):
    rng = np.random.default_rng(31)
    n = 1200
    addrs = _addrs(rng, n)
    rw = (rng.random(n) < 0.2).astype(np.int32)
    arr = np.cumsum(rng.exponential(18.0, n))
    pe = rng.integers(0, 2, n)
    sched = DRAMSchedConfig(policy="frfcfs_cap", reorder_window=32,
                            starvation_cap=8, t_rfc=420, t_refi=9363)
    seq_ev, fast_ev = _trace_pair(
        timing.simulate_faults, timing.simulate_faults_seq, addrs,
        timing.DDR4_2400, sched, rw,
        faults=fc, channel=0, arrival_fpga=arr, pe_id=pe, num_ports=2,
        arb_policy="weighted", weights=(4, 1))
    assert seq_ev == fast_ev
    if fc.injects and fc.transient_ber:
        assert any(e[0] == "replay" for e in seq_ev)
    if fc.outage_windows:
        assert any(e[0] == "outage" for e in seq_ev)


# ---------------------------------------------------------------------------
# 2. tracing never perturbs the model
# ---------------------------------------------------------------------------

def _run_case(name, trace=None):
    if name in SERVING_CASES:
        config, workload, arb_policy, weights = SERVING_CASES[name]
        rows, rw, pe, arr = workload()
        return MemoryController(config).simulate(
            pe, rows, rw, ROW_BYTES, arbiter_policy=arb_policy,
            weights=weights, arrival_cycle=arr, trace=trace)
    config, trace_fn, multiport = CASES[name]
    rows, rw = trace_fn()
    pe = None
    if multiport:
        pe = np.random.default_rng(2).integers(0, config.num_pes,
                                               rows.shape[0])
    return MemoryController(config).simulate(pe, rows, rw, ROW_BYTES,
                                             trace=trace)


@pytest.mark.parametrize("name", [
    "paper_combined_gcn", "paper_combined_multiport_gcn",
    "frfcfs_cap_refresh_gcn", "serving_poisson_frfcfs",
    "serving_hog_victim_weighted", "faults_ecc_storm",
    "faults_channel_outage",
])
def test_traced_run_bit_identical_to_untraced(name):
    base = _run_case(name)
    rec = TraceRecorder()
    traced = _run_case(name, trace=rec)
    assert rec.n_events > 0
    assert base.makespan_fpga_cycles == traced.makespan_fpga_cycles
    assert base.dram_makespan_fpga_cycles \
        == traced.dram_makespan_fpga_cycles
    assert base.breakdown() == traced.breakdown()
    if base.serving is not None:
        for f in ("completion_fpga_cycles", "arrival_fpga_cycles",
                  "service_fpga_cycles"):
            assert np.array_equal(getattr(base.serving, f),
                                  getattr(traced.serving, f))
        assert base.serving.offered_req_per_cycle \
            == traced.serving.offered_req_per_cycle
    if base.dropped is not None:
        assert np.array_equal(base.dropped, traced.dropped)


# ---------------------------------------------------------------------------
# 3. the attribution identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["serving_poisson_frfcfs",
                                  "serving_hog_victim_weighted",
                                  "faults_ecc_storm",
                                  "faults_channel_outage"])
def test_attribution_components_sum_exactly_to_sojourn(name):
    rec = TraceRecorder()
    res = _run_case(name, trace=rec)
    att = CycleAttribution.from_pipeline(res, rec)
    assert att.n == res.n_requests
    # the exact-sum identity, bit for bit, every request
    assert np.array_equal(att.ltr_sum(),
                          res.serving.sojourn_fpga_cycles)
    # every component is the documented non-negative interval length
    # (service carries the ULP residue, so give it one float of slack)
    for k in COMPONENTS:
        lo = -1e-6 if k == "service" else 0.0
        assert (att.components[k] >= lo).all(), k
    # rollups are consistent with the per-request arrays
    tot = att.totals()
    assert sum(tot.values()) == pytest.approx(
        float(res.serving.sojourn_fpga_cycles.sum()))
    per_tenant = att.per_tenant()
    assert sum(r["n"] for r in per_tenant.values()) == att.n
    top = att.top_rows(5)
    assert len(top) <= 5
    assert all(top[i]["sojourn_fpga_cycles"]
               >= top[i + 1]["sojourn_fpga_cycles"]
               for i in range(len(top) - 1))


def test_attribution_blames_the_faulty_machinery():
    """Semantic sanity on the storm case: ECC replays and refresh must
    show up as nonzero components, and the weighted arbiter's hog
    tenant must be dominated by arbitration wait."""
    rec = TraceRecorder()
    res = _run_case("faults_ecc_storm", trace=rec)
    att = CycleAttribution.from_pipeline(res, rec)
    tot = att.totals()
    assert tot["replay"] > 0
    assert tot["refresh"] > 0
    per_tenant = att.per_tenant()
    hog = per_tenant[1]
    assert max(COMPONENTS, key=lambda k: hog[k]) == "arbitration"


def test_closed_loop_attribution_aggregate_view():
    rec = TraceRecorder()
    res = _run_case("frfcfs_cap_refresh_gcn", trace=rec)
    att = CycleAttribution.from_pipeline(res, rec)
    assert att.aggregate_totals is not None
    assert sum(att.totals().values()) == pytest.approx(
        res.makespan_fpga_cycles)
    assert att.totals()["refresh"] > 0
    assert "aggregate" in att.summary_text()


# ---------------------------------------------------------------------------
# 4. the export contract
# ---------------------------------------------------------------------------

def test_chrome_trace_exports_and_validates(tmp_path):
    rec = TraceRecorder()
    _run_case("serving_hog_victim_weighted", trace=rec)
    path = tmp_path / "hog.trace.json"
    counts = tracing.write_chrome_trace(path, rec)
    assert counts["X"] > 0 and counts["C"] > 0 and counts["M"] > 0
    obj = json.loads(path.read_text())
    assert tracing.validate_chrome_trace(obj) == counts
    names = {e["args"]["name"] for e in obj["traceEvents"]
             if e["ph"] == "M"}
    assert {"channel 0", "timeline", "ports"} <= names
    assert any(n.startswith("bank ") for n in names)
    assert any(n.startswith("port ") for n in names)
    # counters exist for both documented series
    cnames = {e["name"] for e in obj["traceEvents"] if e["ph"] == "C"}
    assert "ch0 queue_depth" in cnames
    assert "ch0 reorder_occupancy" in cnames
    assert obj["otherData"]["open_loop"] is True
    assert obj["otherData"]["request_slices_dropped"] == 0


def test_validator_rejects_malformed_traces():
    rec = TraceRecorder()
    _run_case("serving_poisson_frfcfs", trace=rec)
    obj = tracing.to_chrome_trace(rec)
    tracing.validate_chrome_trace(obj)
    with pytest.raises(ValueError):
        tracing.validate_chrome_trace({"no": "traceEvents"})
    bad = json.loads(json.dumps(obj))
    bad["traceEvents"][0]["ph"] = "Q"
    with pytest.raises(ValueError, match="phase"):
        tracing.validate_chrome_trace(bad)
    bad2 = json.loads(json.dumps(obj))
    for e in bad2["traceEvents"]:
        if e["ph"] == "X":
            e["dur"] = -1.0
            break
    with pytest.raises(ValueError, match="dur"):
        tracing.validate_chrome_trace(bad2)


def test_export_slice_cap_is_loud():
    rec = TraceRecorder()
    _run_case("serving_poisson_frfcfs", trace=rec)
    obj = tracing.to_chrome_trace(rec, max_request_slices=100)
    assert obj["otherData"]["request_slices_dropped"] > 0


def test_trace_cli_smoke(tmp_path, capsys):
    from repro.trace import main
    out = tmp_path / "case.trace.json"
    attr = tmp_path / "case.attr.json"
    assert main(["serving_hog_victim_weighted", "--out", str(out),
                 "--attr", str(attr), "--validate"]) == 0
    printed = capsys.readouterr().out
    assert "validated" in printed
    assert "cycle attribution" in printed
    tracing.validate_chrome_trace(json.loads(out.read_text()))
    rollup = json.loads(attr.read_text())
    assert set(rollup["components_total"]) == set(COMPONENTS)
    assert rollup["n_requests"] == 3000


# ---------------------------------------------------------------------------
# satellite regression: closed-loop offered load is 0, never inf
# ---------------------------------------------------------------------------

def test_forced_open_loop_zero_arrivals_offers_zero():
    """A nonempty all-zero-arrival stream pushed through the serving
    datapath (open_loop=True) has no arrival process — offered load
    must report 0.0, not n/0 = inf."""
    rng = np.random.default_rng(1)
    n = 400
    rows = rng.integers(0, 128, n)
    rw = np.zeros(n, np.int32)
    res = MemoryController(MemoryControllerConfig()).simulate(
        None, rows, rw, ROW_BYTES, arrival_cycle=np.zeros(n),
        open_loop=True)
    assert res.serving is not None
    assert res.serving.offered_req_per_cycle == 0.0
    assert np.isfinite(res.serving.offered_req_per_cycle)


def test_open_loop_offered_load_unchanged():
    res = _run_case("serving_poisson_frfcfs")
    s = res.serving
    assert s.offered_req_per_cycle == pytest.approx(
        s.arrival_fpga_cycles.shape[0]
        / float(s.arrival_fpga_cycles.max()))
