"""Each driver's run at smoke widths on the CPU: control flow, the plain
references against the program, faults that must make ``correct`` false,
the control, and the command's exits without a chip."""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench_cells import ROOT, smoke_cell, smoke_run


def _execute(name, **kw):
    return harness.execute(smoke_run(smoke_cell(name), **kw),
                           check_device=False)


@pytest.mark.parametrize("name", ["danube.chat", "mamba2.docqa"])
def test_serve_run_is_correct_and_reports_its_metrics(name):
    res = _execute(name)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert res["checks"]["window_compiles"]["value"] == 0
    assert list(res)[-1] == "checks"


def test_traced_serve_run_drives_the_readers():
    res = _execute("danube.chat", trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 * 3          # trace_units batches of 8
    assert {"busy_s", "window_s", "count"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("trace", [False, True])
def test_gcn_run_is_correct(trace):
    res = _execute("gcn.paper", trace=trace)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_abs_err"]["value"] == 0.0
    if not trace:
        assert set(res["metrics"]) == {"edges_s", "setup_s"}
    assert res["attempted"] % 4 == 0          # whole chunks of steps


@pytest.mark.parametrize("name,arch", [("danube.chat", "h2o-danube-1.8b"),
                                       ("mamba2.docqa", "mamba2-2.7b")])
def test_reference_draws_the_programs_weights_and_agrees(name, arch):
    from repro.launch.serve import Server
    cell = smoke_cell(name)
    server = Server(arch, smoke=True)
    drv = cell.driver()
    cfg = {**cell.config,
           **drv._check_sizes(smoke_run(cell), server.cfg)}
    ref = cell.reference()
    w = ref.weights(cfg)
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), w,
                        server.params)
    assert all(jax.tree.leaves(same))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    got, _ = server.lm.forward(server.params, {"tokens": jnp.asarray(tokens)})
    got = np.asarray(got, np.float32)[..., :cfg["vocab_size"]]
    pos = np.tile(np.arange(24), (2, 1))
    want = ref.logits(w, cfg, tokens, pos)
    # the program computes in bf16; the reference in float32
    assert np.max(np.abs(got - want)) < 0.05 * np.max(np.abs(want))


def _fault_altered_token(server):
    """Every decoded token becomes the model's least likely one."""
    decode = server._decode
    server._decode = lambda p, t, c, n: (lambda lg, cc: (-lg, cc))(
        *decode(p, t, c, n))


def _fault_stale_state(server):
    """The decode step returns its cache unchanged."""
    decode = server._decode
    server._decode = lambda p, t, c, n: (decode(p, t, c, n)[0], c)


def _fault_half_batch(server):
    """Prefill runs the first half of the batch in place of the second."""
    prefill = server._prefill

    def half(p, batch, max_len):
        t = batch["tokens"]
        h = t.shape[0] // 2
        return prefill(p, {"tokens": t.at[h:].set(t[:h])}, max_len)

    server._prefill = half


SERVE_FAULTS = {"altered_token": _fault_altered_token,
                "stale_state": _fault_stale_state,
                "half_batch": _fault_half_batch}


@pytest.mark.parametrize("fault", sorted(SERVE_FAULTS))
@pytest.mark.parametrize("name", ["danube.chat", "mamba2.docqa"])
def test_fault_in_the_timed_path_makes_serve_incorrect(monkeypatch, name,
                                                       fault):
    from repro.launch import serve
    init = serve.Server.__init__

    def broken(self, *a, **kw):
        init(self, *a, **kw)
        SERVE_FAULTS[fault](self)

    monkeypatch.setattr(serve.Server, "__init__", broken)
    res = _execute(name)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def _gather_next_row(gather, mc, table, indices):
    return gather(mc, table, (indices + 1) % table.shape[0])


def _gather_half_block(gather, mc, table, indices):
    rows = gather(mc, table, indices)
    return rows.at[rows.shape[0] // 2:].set(0)


@pytest.mark.parametrize("fault", [_gather_next_row, _gather_half_block])
def test_fault_in_the_timed_path_makes_gcn_incorrect(monkeypatch, fault):
    from repro.core import MemoryController
    gather = MemoryController.gather
    monkeypatch.setattr(MemoryController, "gather",
                        lambda self, t, i: fault(gather, self, t, i))
    res = _execute("gcn.paper")
    assert not res["correct"]
    assert res["checks"]["max_abs_err"]["value"] > 1.0


def test_control_reads_above_the_program():
    cell = smoke_cell("danube.chat")
    rows = cell.driver().readings(cell, [3, 2**35 + 1], 1, "fp8",
                                  smoke=True)
    limits = cell.limits()
    for r in rows:
        assert set(r["program"]) == set(limits)
        # at smoke widths the bf16 program meets float32 but for rounding
        assert all(r["program"][k] < 0.5 * limits[k] for k in limits)
        assert r["control"]["logit_err"] > 2 * limits["logit_err"]
    g = smoke_cell("gcn.paper")
    rows = g.driver().readings(g, [5], 2, "bfloat16", smoke=True)
    assert rows[0]["program"]["max_abs_err"] == 0.0
    assert rows[0]["control"]["max_abs_err"] > 1e-3


@pytest.mark.parametrize("name", ["danube.chat", "gcn.paper",
                                  "mamba2.docqa"])
def test_control_in_the_programs_place_is_incorrect(name):
    cell = smoke_cell(name)
    res = harness.execute(
        smoke_run(cell, control=cell.config["control_precision"]),
        check_device=False)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_cell_without_limits_cannot_be_judged():
    cell = harness.Cell.load("mamba2.docqa")
    with pytest.raises(ValueError, match="no limits"):
        cell.limits()


def _command(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gcn.paper",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_command_without_a_chip_exits_nonzero_and_prints_no_result():
    p = _command(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "accelerator" in p.stderr


def test_command_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not _has_result(p.stdout)
