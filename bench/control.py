#!/usr/bin/env python3
"""The program's readings and its control's, over many seeds in one
process, at the cell's own size: the numbers a cell's limit is set from.

    python3 bench/control.py --workload danube.chat --units 2 --seeds 1 2 3

The control is the plain reference computed in the configuration's
``control_precision`` (float8 e4m3 matmuls for a bf16 model, bfloat16
for float32 features) put in the program's place. Prints one JSON line
per seed, then a summary for each number compared: the largest program
reading (the lower reading) and the smallest control reading (the
upper). The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--units", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cache = ROOT / ".bench_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["TPU_LOG_DIR"] = str(cache / "tpu_logs")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.Cell.load(args.workload)
    try:
        harness.device_info(int(cell.spec["chips"]))
    except harness.NoAccelerator as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    t = time.time()
    rows = cell.driver().readings(cell, args.seeds, args.units,
                                  cell.config["control_precision"])
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower": {k: max(r["program"][k] for r in rows)
                                for k in rows[0]["program"]},
                      "upper": {k: min(r["control"][k] for r in rows)
                                for k in rows[0]["control"]},
                      "seconds": time.time() - t}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
