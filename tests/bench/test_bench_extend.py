"""A configuration, a traffic mix, a cell and a per-layer metric are each
added as new files, and the harness finds them without an edit."""

import json
import shutil

from bench import harness
from bench_cells import ROOT, smoke_run


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p.relative_to(root) for p in root.rglob("*") if p.is_file()}

    config = json.loads((root / "configs" / "gcn-paper.json").read_text())
    config.update(name="gcn-tiny", vertices=2048, features=64,
                  edge_visits=2048 * 40)
    (root / "configs" / "gcn-tiny.json").write_text(json.dumps(config))
    traffic = json.loads((root / "traffic" / "paper_walk.json").read_text())
    traffic.update(block_edges=2048, segments=1024, chunk_steps=2,
                   keep_every=3)
    (root / "traffic" / "tiny_walk.json").write_text(json.dumps(traffic))
    (root / "workloads" / "gcn.tiny.json").write_text(json.dumps(
        {"driver": "gcn", "config": "gcn-tiny", "traffic": "tiny_walk",
         "chips": 1, "trace_units": 3, "limits": {"max_abs_err": 1e-3}}))
    (root / "metrics" / "steps_per_unit.gcn.py").write_text(
        'UNIT = "steps"\n\n\n'
        'def read(ctx):\n'
        '    if ctx.run.cell.spec["driver"] != "gcn":\n'
        '        return None\n'
        '    return ctx.record.steps / ctx.run.cell.spec["trace_units"]\n')

    after = {p.relative_to(root) for p in root.rglob("*") if p.is_file()}
    assert before <= after                      # nothing edited away
    for p in before:
        assert (root / p).read_bytes() == (ROOT / "bench" / p).read_bytes()

    cell = harness.Cell.load("gcn.tiny", root)
    res = harness.execute(smoke_run(cell, trace=True), check_device=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["steps_per_unit.gcn"] == {"value": 2.0,
                                                    "unit": "steps"}
    res = harness.execute(smoke_run(cell), check_device=False)
    assert res["correct"] and "edges_s" in res["metrics"]
