"""GCN aggregation: feature rows gathered through the memory controller.

Set-up makes the graph on the device from the seed (the traffic's
generator) and compiles one step. A step takes the next block of
``block_edges`` consecutive edge visits, gathers their source rows with
``MemoryController(MemoryControllerConfig()).gather`` (the default
engines, as a user builds them) and sums them per destination with
``jax.ops.segment_sum``; row i of its result is vertex ``dst[first] + i``.
Blocks are walked in order and wrap round. Steps are dispatched back to
back; the host waits only on the step before the last chunk
(``chunk_steps``), so the device always has work queued.

End-to-end metric: ``edges_s``, edge visits aggregated (steps x block)
over the whole window, which closes when the last step has finished.

``correct``: the window keeps the result of one step in ``keep_every``
(the phase drawn from the seed) and of the last step. Afterwards up to
``compare_blocks`` of them, drawn from the seed with the last always in,
are recomputed by the plain float32 reference from the same table and
ids; the largest absolute difference must stay under the cell's limit.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Tuple

import jax
import numpy as np


@dataclasses.dataclass
class State:
    data: dict
    step: object
    blocks: int


@dataclasses.dataclass
class Record:
    t_start: float
    t_end: float
    steps: int
    walk: int                                # blocks in the walk
    kept: Dict[int, Tuple[int, object]]      # step -> (block, result)
    distinct: Dict[int, Tuple[int, int]] = dataclasses.field(
        default_factory=dict)                # block -> (sources, dests)


def make_step(block: int, segments: int):
    from repro.core import MemoryController
    from repro.core.config import MemoryControllerConfig
    mc = MemoryController(MemoryControllerConfig())

    def gcn_step(table, src, dst, b):
        first = b * block
        s = jax.lax.dynamic_slice_in_dim(src, first, block)
        d = jax.lax.dynamic_slice_in_dim(dst, first, block)
        rows = mc.gather(table, s)
        return jax.ops.segment_sum(rows, d - d[0], num_segments=segments,
                                   indices_are_sorted=True)

    return jax.jit(gcn_step)


def block_ids(state: State, block: int, b: int):
    return _slices(state.data["src"], state.data["dst"], np.int32(b), block)


@functools.partial(jax.jit, static_argnames=("block",))
def _slices(src, dst, b, block: int):
    return (jax.lax.dynamic_slice_in_dim(src, b * block, block),
            jax.lax.dynamic_slice_in_dim(dst, b * block, block))


def setup(run) -> State:
    import jax.numpy as jnp
    from bench.harness import device_key
    t = run.cell.traffic
    block, segments = t["block_edges"], t["segments"]
    data = run.cell.generator().make(run.cell.config, device_key(run.seed))
    blocks = run.cell.config["edge_visits"] // block
    dst = data["dst"][:blocks * block].reshape(blocks, block)
    span = int(jnp.max(dst[:, -1] - dst[:, 0])) + 1
    if span > segments:
        raise ValueError(f"a block spans {span} destinations, more than "
                         f"the {segments} segments of a step")
    step = make_step(block, segments)
    jax.block_until_ready(step(data["table"], data["src"], data["dst"],
                               np.int32(0)))
    return State(data=data, step=step, blocks=blocks)


def window(run, state: State, stop) -> Record:
    t = run.cell.traffic
    rng = np.random.default_rng([run.seed, 0x6C4])
    keep_every = t["keep_every"]
    phase = int(rng.integers(keep_every))
    table, src, dst = (state.data[k] for k in ("table", "src", "dst"))
    kept = {}
    i = chunks = 0
    pending = None
    t0 = time.time()
    while True:
        with jax.profiler.TraceAnnotation("dispatch"):
            for _ in range(t["chunk_steps"]):
                b = i % state.blocks
                out = state.step(table, src, dst, np.int32(b))
                if i % keep_every == phase:
                    kept[i] = (b, out)
                i += 1
        with jax.profiler.TraceAnnotation("wait"):
            if pending is not None:
                pending.block_until_ready()
        pending = out
        chunks += 1
        if stop(time.time() - t0, chunks):
            break
    out.block_until_ready()
    t1 = time.time()
    kept[i - 1] = (b, out)
    return Record(t0, t1, i, state.blocks, kept)


def end_to_end(run, rec: Record) -> dict:
    edges = rec.steps * run.cell.traffic["block_edges"]
    return {"edges_s": (edges / (rec.t_end - rec.t_start), "edges/s")}


def counts(rec: Record) -> Tuple[int, int]:
    return rec.steps, 0


def release(state: State) -> None:
    state.step = None


def distinct(state: State, block: int, b: int) -> Tuple[int, int]:
    import jax.numpy as jnp
    s, d = block_ids(state, block, b)
    s = jnp.sort(s)
    return (int(jnp.sum(s[1:] != s[:-1])) + 1,
            int(jnp.sum(d[1:] != d[:-1])) + 1)


def reading(run, state: State, rec: Record, control=None) -> float:
    """The largest absolute difference between the kept results drawn
    from the seed (the last always in) and the float32 reference; with
    ``control`` set, the reference in that precision takes the program's
    results' place."""
    t = run.cell.traffic
    ref = run.cell.reference()
    last = max(rec.kept)
    rng = np.random.default_rng([run.seed, 0xC0DE])
    others = sorted(k for k in rec.kept if k != last)
    pick = rng.choice(others, size=min(t["compare_blocks"] - 1, len(others)),
                      replace=False) if others else []
    err = 0.0
    for i in [last, *map(int, pick)]:
        b, out = rec.kept[i]
        s, d = block_ids(state, t["block_edges"], b)
        want = ref.aggregate(state.data["table"], s, d, t["segments"])
        if control:
            out = ref.aggregate(state.data["table"], s, d, t["segments"],
                                control)
        err = max(err, float(np.max(np.abs(np.asarray(out)
                                           - np.asarray(want)))))
    return err


def check(run, state: State, rec: Record):
    from bench.harness import Check
    release(state)
    value = reading(run, state, rec, run.control)
    if run.trace:
        block = run.cell.traffic["block_edges"]
        for b in sorted({i % rec.walk for i in range(rec.steps)}):
            rec.distinct[b] = distinct(state, block, b)
    return [Check("max_abs_err", value,
                  float(run.cell.limits()["max_abs_err"]))]


def readings(cell, seeds, units: int, precision: str, smoke: bool = False):
    """The program's and the control's readings on each seed: the graph
    of each seed made anew, a window of ``units`` chunks."""
    import gc
    from bench.harness import Run
    out = []
    for s in seeds:
        run = Run(cell=cell, seed=s, seconds=0.0, trace=False,
                  t_start=time.time(), smoke=smoke)
        state = setup(run)
        rec = window(run, state, lambda elapsed, done: done >= units)
        release(state)
        out.append({"seed": s,
                     "program": {"max_abs_err": reading(run, state, rec)},
                     "control": {"max_abs_err": reading(run, state, rec,
                                                        precision)}})
        del state, rec
        gc.collect()
    return out
