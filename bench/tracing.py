"""From the profiler's trace to numbers.

``Capture`` records a window with ``jax.profiler`` (Python tracer off)
and reads the ``.xplane.pb`` into a plain dict::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

Everything below works on that dict, so a small trace kept as JSON tests
the reduction. Device planes are ``/device:<kind>:<n>``; their ``XLA Ops``
line holds one event per executed operation and their ``XLA Modules``
line one per executed program. The harness's own host spans
(``TraceAnnotation``) lie on a host plane on the same clock; the window
is the span named ``WINDOW_SPAN``.
"""

from __future__ import annotations

import glob
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")


class Capture:
    """Profile what runs inside ``with``; ``.trace`` holds the result."""

    def __init__(self):
        self.trace: Optional[dict] = None

    def __enter__(self):
        import jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                path = glob.glob(f"{self._dir}/**/*.xplane.pb",
                                 recursive=True)[0]
                self.trace = read_xplane(path)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def read_xplane(path: str) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# --------------------------------------------------------------------------
# Reduction
# --------------------------------------------------------------------------

def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if _DEVICE.match(p["name"])]


def host_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/host:")]


def line_events(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def window(trace: dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the harness's window span."""
    for plane in host_planes(trace):
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def clip(events: Iterable[list], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Intervals of the events, clipped to [t0, t1]."""
    out = []
    for _, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(plane: dict, t0: float, t1: float) -> float:
    """Time in [t0, t1] in which some operation ran on this device."""
    return sum(b - a for a, b in union(clip(line_events(plane, OPS_LINE),
                                             t0, t1)))


def mean_busy_s(trace: dict, t0: float, t1: float) -> float:
    planes = device_planes(trace)
    if not planes:
        return 0.0
    return sum(busy_ns(p, t0, t1) for p in planes) / len(planes) / 1e9


def idle_share(trace: dict) -> Optional[float]:
    """Percent of the window in which no operation ran, on the idlest
    device: 100 * (1 - busy / window); None without a device plane."""
    t0, t1 = window(trace)
    planes = device_planes(trace)
    if not planes:
        return None
    return max(100.0 * (1.0 - busy_ns(p, t0, t1) / (t1 - t0))
               for p in planes)


def module_name(event_name: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0].strip()


def module_time(trace: dict, name: str, plane: int = 0) -> Tuple[float, int]:
    """(seconds, executions) of the program ``name`` on one device, within
    the window."""
    spans = module_spans(trace, name, plane)
    return sum(b - a for a, b in spans) / 1e9, len(spans)


def module_spans(trace: dict, name: str, plane: int = 0) -> List[Tuple[float, float]]:
    t0, t1 = window(trace)
    planes = device_planes(trace)
    if len(planes) <= plane:
        return []
    return clip([e for e in line_events(planes[plane], MODULES_LINE)
                 if module_name(e[0]) == name], t0, t1)


def op_name(event_name: str) -> str:
    """An operation's short name: ``%sort.5 = s32[65536]{0} sort(...)``
    -> ``sort.5 s32[65536]`` (its HLO name and the first word of its
    result type)."""
    name, _, rest = event_name.partition(" = ")
    kind = rest.split(" ", 1)[0].split("{", 1)[0]
    return f"{name.lstrip('%')} {kind}".strip()


def op_time(trace: dict, pattern: str, within: Optional[str] = None,
            plane: int = 0) -> float:
    """Seconds of the operations whose HLO name (``op_name`` without its
    type) matches ``pattern`` (a regular expression, matched at the
    start), optionally only those that ran inside executions of the
    program ``within``."""
    t0, t1 = window(trace)
    rx = re.compile(pattern)
    planes = device_planes(trace)
    if len(planes) <= plane:
        return 0.0
    ops = [e for e in line_events(planes[plane], OPS_LINE)
           if rx.match(op_name(e[0]))]
    spans = clip(ops, t0, t1)
    if within is not None:
        mods = module_spans(trace, within, plane)
        spans = [s for s in spans
                 if any(a <= s[0] and s[1] <= b for a, b in mods)]
    return sum(b - a for a, b in spans) / 1e9


def breakdown(trace: dict, t0: float, t1: float, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps, each named by the innermost host span that covers it."""
    if not device_planes(trace):
        return {"device_ops": [], "idle_gaps": []}
    dev = device_planes(trace)[0]
    by_op: Dict[str, float] = {}
    for name, start, dur in line_events(dev, OPS_LINE):
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            name = op_name(name)
            by_op[name] = by_op.get(name, 0.0) + (b - a) / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    busy = union(clip(line_events(dev, OPS_LINE), t0, t1))
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    spans = [e for p in host_planes(trace) for line in p["lines"]
             for e in line["events"] if e[2] > 0]
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [e for e in spans if e[1] <= mid <= e[1] + e[2]]
        label = min(cover, key=lambda e: e[2])[0] if cover else "no host span"
        named.append([label, (b - a) / 1e9])
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": named}
