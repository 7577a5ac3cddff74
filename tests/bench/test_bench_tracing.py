"""The reduction from a profiler trace to numbers, on a small trace kept
in ``data/``, against values counted by hand.

The trace: a window span over [1000, 11000] ns on the host; on device 0
a prefill program clipped by the window's start, two decode programs and
one GCN step, with overlapping and adjacent operations; device 1 busy
over [1000, 3000] only.
"""

import json
from pathlib import Path

import pytest

from bench import tracing

TRACE = json.loads((Path(__file__).parent / "data" /
                    "small_trace.json").read_text())
COLLECTIVES = r"all-reduce|all-gather|reduce-scatter|collective-permute"


def test_window_is_the_harness_span():
    assert tracing.window(TRACE) == (1000.0, 11000.0)


def test_busy_is_the_union_of_operations_within_the_window():
    dev0, dev1 = tracing.device_planes(TRACE)
    # [1000,1500] + [2000,2900] + [4000,5000] + [6000,7500] + [10500,11000]
    assert tracing.busy_ns(dev0, 1000, 11000) == 4400
    assert tracing.busy_ns(dev1, 1000, 11000) == 2000
    assert tracing.mean_busy_s(TRACE, 1000, 11000) == pytest.approx(3.2e-6)


def test_idle_share_is_the_idlest_device():
    # device 0 idles 5600 of 10000 ns, device 1 8000
    assert tracing.idle_share(TRACE) == pytest.approx(80.0)


def test_module_time_counts_executions_clipped_to_the_window():
    assert tracing.module_time(TRACE, "jit_decode_step") == \
        (pytest.approx(2e-6), 2)
    assert tracing.module_time(TRACE, "jit__lambda") == \
        (pytest.approx(5e-7), 1)
    assert tracing.module_time(TRACE, "jit_absent") == (0.0, 0)


def test_op_names_are_the_hlo_name_and_result_type():
    assert tracing.op_name("%fusion.1 = bf16[8,6912]{1,0:T(8,128)} "
                           "fusion(bf16[8,2560]{1,0} %p), kind=kOutput") \
        == "fusion.1 bf16[8,6912]"
    assert tracing.op_name("copy.8") == "copy.8"


def test_sort_time_inside_one_program():
    assert tracing.op_time(TRACE, r"sort") == pytest.approx(1.9e-6)
    assert tracing.op_time(TRACE, r"sort", within="jit_gcn_step") == \
        pytest.approx(1.5e-6)


def test_collective_share_of_busy_time():
    busy = tracing.busy_ns(tracing.device_planes(TRACE)[0], 1000, 11000)
    share = tracing.op_time(TRACE, COLLECTIVES) * 1e9 / busy
    assert share == pytest.approx(500 / 4400)


def test_breakdown_names_gaps_by_the_innermost_host_span():
    b = tracing.breakdown(TRACE, 1000.0, 11000.0, top=3)
    assert b["device_ops"][0] == ["sort.5 s32[65536]", pytest.approx(1e-6)]
    assert [n for n, _ in b["device_ops"][1:]] == ["fusion.1", "fusion.4"]
    assert b["idle_gaps"] == [["dispatch", pytest.approx(3e-6)],
                              ["Server.serve", pytest.approx(1.1e-6)],
                              ["Server.serve", pytest.approx(1e-6)]]


def test_a_trace_without_the_window_span_is_an_error():
    bare = {"planes": [p for p in TRACE["planes"]
                       if not p["name"].startswith("/host:")]}
    with pytest.raises(ValueError, match="bench.window"):
        tracing.window(bare)
