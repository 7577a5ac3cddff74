"""The program's own spans in a profiler trace.

``Server`` (``repro.launch.serve``) writes host spans named ``serve`` and
``serve.*`` (``serve.batch``, ``serve.step``, ``serve.model_memory`` ...)
on the device planes' clock. These helpers find them in the dict of
``bench.tracing.read_xplane`` and measure the device inside them.
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

from bench import tracing


def spans(trace: dict, name: str) -> List[Tuple[float, float]]:
    """(start_ns, end_ns) of the host spans called ``name`` that start
    inside the window, in order of start."""
    t0, t1 = tracing.window(trace)
    return sorted((start, start + dur)
                  for plane in tracing.host_planes(trace)
                  for line in plane["lines"]
                  for n, start, dur in line["events"]
                  if n == name and t0 <= start < t1)


def inside(inner: Iterable[Tuple[float, float]],
           outer: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The spans of ``inner`` that lie within the span ``outer``."""
    return [s for s in inner if outer[0] <= s[0] and s[1] <= outer[1]]


def idle_ns(trace: dict, intervals: Iterable[Tuple[float, float]],
            plane: int = 0) -> float:
    """Time within the intervals in which no operation ran on the device."""
    dev = tracing.device_planes(trace)[plane]
    return sum((b - a) - tracing.busy_ns(dev, a, b) for a, b in intervals)
