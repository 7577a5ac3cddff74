"""The program's spans, reduced to the per-layer metrics that read them,
on a hand-made trace kept in ``data/``, against values counted by hand.

The trace: a window over [0, 20000] ns. Two ``serve`` calls of one batch
each: batch 0 (2 rows, 2 steps, 3 new tokens) with step spans [1000,
2500] and [4000, 5500] and a replay span of 1200 ns; batch 1 (2 rows, 3
steps, 4 new tokens) with step spans of 1000 ns at 11000, 13000 and
15000 and a replay span of 800 ns. On the device: two prefill programs
(600 and 200 ns), five decode programs (1500, 1500, 1000, 1000, 1000 ns),
a token read's ``jit_dynamic_slice`` at [1500, 1550], and an unrelated
program at [19000, 19500]; each decode program holds a ``while`` with a
fusion nested inside.
"""

import copy
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, program_trace

DATA = Path(__file__).parent / "data"
TRACE = json.loads((DATA / "program_spans.json").read_text())
BARE = json.loads((DATA / "small_trace.json").read_text())
READERS = harness.metric_readers()
NEW = ("decode_gap_ms.serve", "sim_replay_ms.serve",
       "decode_slot_use.serve")


def served(new_tokens):
    return SimpleNamespace(prompts=[np.zeros(4, np.int32)] * len(new_tokens),
                           new_tokens=list(new_tokens))


def context(trace, driver="serve"):
    run = SimpleNamespace(cell=SimpleNamespace(spec={"driver": driver}))
    record = SimpleNamespace(served=[served([1, 2]), served([1, 3])])
    return harness.ReadContext(run=run, record=record, trace=trace,
                               peaks=None)


def test_spans_in_order_and_nesting():
    batches = program_trace.spans(TRACE, "serve.batch")
    assert batches == [(200, 8200), (10600, 17600)]
    steps = program_trace.spans(TRACE, "serve.step")
    assert [len(program_trace.inside(steps, b)) for b in batches] == [2, 3]
    assert steps[0] == (1000, 2500)


def test_decode_gap_is_device_idle_inside_the_step_spans():
    # idle per step: 1500-50-200, 1500-200, 1000-400, 1000-500, 1000-300
    assert READERS["decode_gap_ms.serve"].read(context(TRACE)) == \
        pytest.approx(4350 / 5 / 1e6)


def test_decode_gap_raises_when_steps_and_decodes_differ():
    trace = copy.deepcopy(TRACE)
    line = trace["planes"][0]["lines"][0]
    i = [e[0] for e in line["events"]].index("serve.step")
    del line["events"][i]
    with pytest.raises(ValueError, match="4 serve.step spans but 5"):
        READERS["decode_gap_ms.serve"].read(context(trace))


def test_sim_replay_is_the_mean_replay_span():
    assert READERS["sim_replay_ms.serve"].read(context(TRACE)) == \
        pytest.approx(1000 / 1e6)


def test_decode_slot_use_is_new_tokens_over_rows_times_steps():
    # (1 + 2 + 1 + 3) tokens over 2 x 2 + 2 x 3 slots
    assert READERS["decode_slot_use.serve"].read(context(TRACE)) == \
        pytest.approx(70.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_are_silent_without_the_programs_spans(name):
    # a program without the spans (the small trace has the harness's
    # own, and none of the program's)
    assert READERS[name].read(context(BARE)) is None
    assert READERS[name].read(context(TRACE, driver="gcn")) is None


def test_profiled_smoke_serve_writes_the_spans_it_documents(tmp_path):
    """A smoke ``Server`` profiled on the CPU and read with
    ``bench.tracing.read_xplane``: the program's spans nest as
    ``repro.launch.serve`` documents, and carry their ``batch`` and
    ``step`` args."""
    import jax
    from bench import tracing
    from repro.launch.serve import Request, Server

    server = Server("h2o-danube-1.8b", smoke=True)

    def requests():
        # admitted as two batches: rids 0 and 1 together, 2 alone
        return [Request(0, np.arange(5, dtype=np.int32), 3, 0),
                Request(1, np.arange(7, dtype=np.int32) + 3, 2, 1),
                Request(2, np.arange(4, dtype=np.int32) + 9, 4, 200)]

    server.serve(requests())        # compiles outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        server.serve(requests())
        server.serve(requests()[2:])
    jax.profiler.stop_trace()
    path = str(next(tmp_path.glob("**/*.xplane.pb")))
    trace = tracing.read_xplane(path)

    def spans(name):
        return program_trace.spans(trace, name)

    assert len(spans("serve")) == len(spans("serve.admit")) == 2
    assert len(spans("serve.model_memory")) == 2
    batches = spans("serve.batch")
    assert len(batches) == 3
    steps, reads, dispatches, prefills = (
        spans(n) for n in ("serve.step", "serve.read_tokens",
                           "serve.dispatch", "serve.prefill"))
    # one prefill and max_new_tokens steps a batch, each step one token
    # read and one dispatch
    assert [len(program_trace.inside(prefills, b)) for b in batches] == \
        [1, 1, 1]
    assert [len(program_trace.inside(steps, b)) for b in batches] == \
        [3, 4, 4]
    for s in steps:
        assert len(program_trace.inside(reads, s)) == 1
        assert len(program_trace.inside(dispatches, s)) == 1
    assert len(reads) == len(dispatches) == len(steps) == 3 + 4 + 4

    # the args, as the profiler hands them out: the batch counter rises
    # across calls (the warm-up ran batches 0 and 1)
    args = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve"):
                    args.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))

    def args_of(name):
        return [a for _, a in sorted(args[name], key=lambda x: x[0])]

    assert args_of("serve") == [{}, {}]
    assert args_of("serve.model_memory") == [{}, {}]
    assert args_of("serve.batch") == [{"batch": b} for b in (2, 3, 4)]
    assert args_of("serve.prefill") == [{"batch": b} for b in (2, 3, 4)]
    assert args_of("serve.step") == [
        {"batch": b, "step": k} for b, n in ((2, 3), (3, 4), (4, 4))
        for k in range(n)]
    assert args_of("serve.read_tokens") == args_of("serve.dispatch") == [
        {"batch": b} for b, n in ((2, 3), (3, 4), (4, 4)) for _ in range(n)]
