"""Finds a cell's files by name, times set-up and the window, and prints.

A run is: set-up (``driver.setup``: the system under test, its inputs
from the seed, every shape of the cell warmed), then one window with the
profiler off (``--trace 0``: the cell's end-to-end metrics) or a short
window under the profiler (``--trace 1``: the per-layer metrics that the
readers in ``metrics/`` find), then the comparison with the plain
reference (``driver.check``), which decides ``correct``. A compilation
inside the window fails the run.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent

#: The JAX event that marks a compilation (or a load from the persistent
#: compile cache) of a new program in this process.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------
# Files found by name
# --------------------------------------------------------------------------

def load_json(kind: str, name: str, root: Path = BENCH) -> dict:
    return json.loads((root / kind / f"{name}.json").read_text())


def load_module(path: Path):
    """Import a file of the benchmark by its path (names hold dots)."""
    name = "bench._files." + "".join(
        c if c.isalnum() else "_" for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads``: the cell file and what it names."""

    name: str
    spec: dict          # workloads/<cell>.json
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<mix>.json
    root: Path = BENCH

    @classmethod
    def load(cls, name: str, root: Path = BENCH) -> "Cell":
        spec = load_json("workloads", name, root)
        return cls(name=name, spec=spec,
                   config=load_json("configs", spec["config"], root),
                   traffic=load_json("traffic", spec["traffic"], root),
                   root=root)

    def limits(self) -> dict:
        """The limit of each number compared. A cell gets them only from
        readings of the program and its control at the cell's own size;
        until then its file holds none, and it cannot be judged."""
        if "limits" not in self.spec:
            raise ValueError(f"cell {self.name!r} has no limits: set them "
                             "from readings at its size (bench/control.py)")
        return self.spec["limits"]

    def driver(self):
        return load_module(self.root / "drivers" / f"{self.spec['driver']}.py")

    def generator(self):
        return load_module(
            self.root / "generators" / f"{self.traffic['generator']}.py")

    def reference(self):
        return load_module(self.root / "configs" / self.config["reference"])


def metric_readers(root: Path = BENCH) -> Dict[str, Any]:
    """Every per-layer reader, by metric name (the file's name)."""
    return {p.name[:-3]: load_module(p)
            for p in sorted((root / "metrics").glob("*.py"))}


def peaks(kind: str, root: Path = BENCH) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads((root / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json; add its "
                       "published peaks with their source")
    return table["devices"][kind]


# --------------------------------------------------------------------------
# A run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    #: CPU tests only: the program's smoke widths, no chip, no peaks
    smoke: bool = False
    #: the control: the plain reference in this precision (the
    #: configuration's ``control_precision``) put in the program's place
    #: when the outputs are compared; the benchmark's own runs leave it off
    control: Optional[str] = None

    def stop_rule(self) -> Callable[[float, int], bool]:
        """When the window closes: after ``--seconds`` of work, or (traced)
        after the cell's fixed number of units."""
        if self.trace:
            units = int(self.cell.spec["trace_units"])
            return lambda elapsed, done: done >= units
        return lambda elapsed, done: elapsed >= self.seconds


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``value <= limit`` passes."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def device_key(seed: int):
    """A JAX key from any whole-number seed (more than 32 bits)."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class CompileCounter:
    """Counts programs compiled or loaded while it is open."""

    def __init__(self):
        self.count = 0
        self.names: List[str] = []
        self._open = False

    def _listen(self, event: str, duration: float, **kw) -> None:
        if self._open and event == COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._open = True
        return self

    def __exit__(self, *exc):
        self._open = False


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        raise NoAccelerator(
            f"need {chips} accelerator chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
    peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def execute(run: Run, *, check_device: bool = True) -> dict:
    """One whole run; returns the result line as a dict."""
    import jax
    chips = int(run.cell.spec["chips"])
    if check_device:
        device = device_info(chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    drv = run.cell.driver()
    state = drv.setup(run)
    setup_s = time.time() - run.t_start

    trace = None
    with CompileCounter() as compiles:
        if run.trace:
            from bench import tracing
            with tracing.Capture() as cap:
                record = drv.window(run, state, run.stop_rule())
            trace = cap.trace
        else:
            record = drv.window(run, state, run.stop_rule())
    if check_device:
        device["memory_peak_bytes"] = memory_peak(chips)
    # the driver frees the program's state before its reference runs
    checks = [Check("window_compiles", compiles.count, 0)]
    checks += drv.check(run, state, record)
    if compiles.count:
        print(f"compiled in the window: {compiles.names}", file=sys.stderr)

    if run.trace:
        from bench import tracing
        ctx = ReadContext(run=run, record=record, trace=trace,
                          peaks=None if run.smoke else peaks(device["kind"]))
        metrics = {}
        for name, reader in metric_readers(run.cell.root).items():
            value = reader.read(ctx)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": reader.UNIT}
        t0, t1 = tracing.window(trace)
        if check_device and not tracing.device_planes(trace):
            raise RuntimeError("the trace holds no device operations")
        device["busy_s"] = tracing.mean_busy_s(trace, t0, t1)
        device["window_s"] = (t1 - t0) / 1e9
        breakdown = tracing.breakdown(trace, t0, t1)
    else:
        metrics = {name: {"value": float(v), "unit": unit}
                   for name, (v, unit) in drv.end_to_end(run, record).items()}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        breakdown = None

    attempted, failed = drv.counts(record)
    result = {"correct": all(c.ok for c in checks) and failed == 0,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader reads: the trace, the driver's record of
    the window, and the chip's peaks."""

    run: Run
    record: Any
    trace: Optional[dict]
    peaks: Optional[dict]


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} <= {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)


def main(workload: str, *, seed: int, seconds: float, trace: bool,
         t_start: float) -> int:
    if not (BENCH / "workloads" / f"{workload}.json").exists():
        print(f"no cell named {workload!r}", file=sys.stderr)
        return 2
    run = Run(cell=Cell.load(workload), seed=seed, seconds=seconds,
              trace=trace, t_start=t_start)
    try:
        result = execute(run)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for m in result["metrics"].values():
        if not math.isfinite(m["value"]):
            raise ValueError(f"non-finite metric in {result['metrics']}")
    emit(result)
    return 0
