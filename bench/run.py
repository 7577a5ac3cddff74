#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload danube.chat --seed 7 --seconds 45 --trace 0

Prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``: every number compared with its limit.
The same checks are the last lines of standard error. Without an
accelerator, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""

import time

T_START = time.time()   # set-up is timed from here: before any import

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # Compiled programs and libtpu's logs stay inside the checkout, at a
    # fixed path: the path is part of the compile cache's key.
    cache = ROOT / ".bench_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["TPU_LOG_DIR"] = str(cache / "tpu_logs")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench import harness
    return harness.main(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
