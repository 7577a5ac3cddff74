"""Smoke-size cells for the benchmark's CPU tests: the same drivers,
generators, references and readers, at the program's smoke widths."""

import time
from pathlib import Path

from bench import harness

ROOT = harness.BENCH.parent

#: traffic at smoke size: two prompt buckets, short answers
SMOKE_TRAFFIC = {
    "danube.chat": {"pool_batches": 3,
                    "prompt": {"median": 8, "sigma": 0.8, "min": 4,
                               "max": 16, "buckets": [8, 16]},
                    "answers": {"tokens": [3, 6], "per_batch": [6, 2]}},
    "mamba2.docqa": {"pool_batches": 3,
                     "prompt": {"median": 12, "sigma": 0.6, "min": 8,
                                "max": 24, "buckets": [16, 24]},
                     "answers": {"tokens": [4], "per_batch": [8]}},
    "gcn.paper": {"block_edges": 4096, "segments": 2048, "chunk_steps": 4,
                  "keep_every": 5},
}
SMOKE_CONFIG = {"gcn.paper": {"vertices": 4096, "features": 128,
                              "edge_visits": 4096 * 24 + 100}}
#: limits at smoke widths, from CPU readings over six seeds
#: (``readings``): the program at most 0.025 / 0.071 (gap / err) and the
#: fp8 control at least 0.209 / 0.478 (mamba2.docqa, danube.chat); the
#: program 0 and the bfloat16 control at least 0.40 (gcn.paper). The
#: limits of a cell's own file hold at its own size, where no smoke run
#: can read.
SMOKE_LIMITS = {"mamba2.docqa": {"logit_gap": 0.1, "logit_err": 0.2},
                "danube.chat": {"logit_gap": 0.1, "logit_err": 0.2},
                "gcn.paper": {"max_abs_err": 1e-3}}


def smoke_cell(name: str, root: Path = harness.BENCH) -> harness.Cell:
    cell = harness.Cell.load(name, root)
    cell.traffic = {**cell.traffic, **SMOKE_TRAFFIC.get(name, {})}
    cell.config = {**cell.config, **SMOKE_CONFIG.get(name, {})}
    cell.spec = {**cell.spec, "limits": SMOKE_LIMITS[name]}
    return cell


def smoke_run(cell: harness.Cell, seed: int = 2**31 + 77,
              trace: bool = False, control=None) -> harness.Run:
    return harness.Run(cell=cell, seed=seed, seconds=0.5, trace=trace,
                       t_start=time.time(), smoke=True, control=control)
