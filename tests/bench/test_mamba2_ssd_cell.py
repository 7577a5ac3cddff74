"""The judged Mamba-2 cell, ``mamba2.ssd_docqa``, at smoke widths on the
CPU: the program is correct, the control and the faults are not, and the
prefill roofline reads a traced run."""

import math

import jax
import jax.numpy as jnp
import pytest

from bench import counts, harness, program_trace, tracing
from bench_cells import smoke_run

NAME = "mamba2.ssd_docqa"

#: the cell's ``docqa`` traffic at smoke size: two prompt buckets, every
#: answer 4 tokens
SMOKE_TRAFFIC = {"pool_batches": 3,
                 "prompt": {"median": 12, "sigma": 0.6, "min": 8, "max": 24,
                            "buckets": [16, 24]},
                 "answers": {"tokens": [4], "per_batch": [8]}}
#: limits at smoke widths, from CPU readings of the same configuration
#: and traffic over six seeds: the program at most 0.025 / 0.071 (gap /
#: err), the fp8 control at least 0.209 / 0.478. The limits of the cell's
#: own file hold at its own size, where no smoke run can read.
SMOKE_LIMITS = {"logit_gap": 0.1, "logit_err": 0.2}


def smoke_cell() -> harness.Cell:
    cell = harness.Cell.load(NAME)
    cell.traffic = {**cell.traffic, **SMOKE_TRAFFIC}
    cell.spec = {**cell.spec, "limits": SMOKE_LIMITS}
    return cell


def _execute(**kw):
    return harness.execute(smoke_run(smoke_cell(), **kw), check_device=False)


def test_cell_file_names_the_served_model_and_its_traffic():
    cell = harness.Cell.load(NAME)
    assert cell.spec["driver"] == "serve" and cell.spec["chips"] == 1
    assert cell.config["arch"] == "mamba2-2.7b" and cell.config["reduced"] == []
    assert cell.spec["traffic"] == "docqa"
    assert cell.spec["trace_units"] >= 3
    assert set(cell.limits()) == {"logit_gap", "logit_err"}


def test_program_is_correct():
    res = _execute()
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert res["checks"]["window_compiles"]["value"] == 0


def test_control_in_the_programs_place_is_incorrect():
    cell = smoke_cell()
    res = harness.execute(
        smoke_run(cell, control=cell.config["control_precision"]),
        check_device=False)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def _fault_altered_token(server):
    """Every decoded token becomes the model's least likely one."""
    decode = server._decode
    server._decode = lambda p, t, c, n: (lambda lg, cc: (-lg, cc))(
        *decode(p, t, c, n))


def _fault_stale_state(server):
    """The decode step hands back the state it was given, unchanged: a
    copy taken before the call, since the call donates the state."""
    decode = server._decode

    def stale(p, t, c, n):
        old = jax.tree.map(jnp.copy, c)
        return decode(p, t, c, n)[0], old

    server._decode = stale


def _fault_half_batch(server):
    """Prefill runs the first half of the batch in place of the second."""
    prefill = server._prefill

    def half(p, batch, max_len):
        t = batch["tokens"]
        h = t.shape[0] // 2
        return prefill(p, {"tokens": t.at[h:].set(t[:h])}, max_len)

    server._prefill = half


FAULTS = {"altered_token": _fault_altered_token,
          "stale_state": _fault_stale_state,
          "half_batch": _fault_half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_makes_the_cell_incorrect(monkeypatch,
                                                          fault):
    from repro.launch import serve
    init = serve.Server.__init__

    def broken(self, *a, **kw):
        init(self, *a, **kw)
        FAULTS[fault](self)

    monkeypatch.setattr(serve.Server, "__init__", broken)
    res = _execute()
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_prefill_roofline_reads_a_traced_run():
    """A traced smoke run, with a device plane on which each prefill lasts
    as long as its padded batch needs at the v5e's peak: the reading is
    the prompts' own share of the padded work, finite and under 100."""
    run = smoke_run(smoke_cell(), trace=True)
    drv = run.cell.driver()
    state = drv.setup(run)
    with tracing.Capture() as cap:
        record = drv.window(run, state, run.stop_rule())
    trace = cap.trace
    peaks = harness.peaks("TPU v5 lite")
    cfg = record.cfg
    prefills = program_trace.spans(trace, "serve.prefill")
    assert len(prefills) == len(record.served) == run.cell.spec["trace_units"]
    events, real, padded = [], 0, 0
    for (start, _), s in zip(prefills, record.served):
        need = len(s.prompts) * (counts.tokens_flops(cfg, 1, s.width)
                                 + counts.head_flops(cfg))
        events.append(["jit__lambda(7)", start,
                       1e9 * need / peaks["bf16_flops_per_s"]])
        padded += need
        real += sum(counts.tokens_flops(cfg, 1, len(p))
                    + counts.head_flops(cfg) for p in s.prompts)
    trace["planes"].append({"name": "/device:TPU:0", "lines": [
        {"name": tracing.MODULES_LINE, "events": events}]})
    ctx = harness.ReadContext(run=run, record=record, trace=trace,
                              peaks=peaks)
    reader = harness.metric_readers()["prefill_roofline"]
    value = reader.read(ctx)
    assert math.isfinite(value) and 0 < value < 100
    assert value == pytest.approx(100 * real / padded, rel=1e-6)
    assert reader.UNIT == "%"
    # without peaks (no chip) the reader reads nothing
    assert reader.read(harness.ReadContext(run=run, record=record,
                                           trace=trace, peaks=None)) is None
