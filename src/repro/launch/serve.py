"""Serving driver: the memory-controller scheduler applied to requests.

The paper's scheduler batches memory requests under (batch_size, timeout)
bounds before servicing them; this driver applies the identical policy to
*inference requests*: arrivals accumulate into a prefill batch until the
batch is full or the timeout expires (``core.scheduler.form_batches`` — the
same code path the DRAM scheduler uses), then the batch is prefetched and
decoded in lockstep. Cache-line vs DMA routing maps to decode (latency-
critical, prioritized) vs prefill (bulk, throughput) — decode steps run
ahead of admitting new prefill work, mirroring the cache-priority rule.

Each served batch also drives the *modeled* memory system: the KV-cache
access stream of prefill + lockstep decode (page reads/appends per
request, stamped with open-loop arrival times) is replayed through
``MemoryController.simulate`` (ARCHITECTURE §9), so a serve run reports
modeled p50/p95/p99 memory sojourn per tenant next to the functional
outputs. ``Request.tenant`` maps to the controller port — weighted
arbitration + starvation cap is what protects a latency-SLO tenant from
a bandwidth hog sharing the controller (tests/launch/test_serve.py).

Every call is traced on the profiler's host timeline
(``jax.profiler.TraceAnnotation``, the device planes' clock) and timed
with host counters, always on; the spans cost nothing to speak of while
no profiler runs, and add no device sync::

    serve
      serve.admit
      serve.batch (batch)
        serve.prefill (batch)           _prefill and the first argmax
        serve.step (batch, step)        one per decode step
          serve.read_tokens (batch)     the int(tok[i]) reads
          serve.dispatch (batch)        _decode, argmax, astype, cur + 1
      serve.model_memory                the modeled KV replay

``batch`` counts the server's batches across calls, so every span of one
lockstep batch carries the same number in a trace viewer. ``ServeStats``
keeps the same phases' host seconds, and ``Request.token_times`` the
host time at which each token reached the host.

CPU-runnable demo: ``python -m repro.launch.serve --arch yi-34b --smoke``.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_arch
from repro.core.config import MemoryControllerConfig, SchedulerConfig
from repro.core.controller import MemoryController
from repro.core.scheduler import form_batches
from repro.launch.compile_cache import use_compile_cache
from repro.models.lm import build_lm

#: KV page granularity of the modeled access stream (bytes per token row)
KV_PAGE_BYTES = 256


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 16
    arrival_cycle: int = 0
    tenant: int = 0             # controller port this request issues from
    output: Optional[List[int]] = None
    #: ``time.perf_counter()`` when each output token reached the host
    token_times: Optional[List[float]] = None


@dataclasses.dataclass
class BatchTimes:
    """Host seconds of one lockstep batch, by phase."""

    batch: int
    prefill_s: float    # prefill dispatch until the first token is read
    decode_s: float     # first token read until the last step dispatched
    read_s: float       # in ``serve.read_tokens`` (waits on the device)
    dispatch_s: float   # in ``serve.dispatch``


@dataclasses.dataclass
class ServeStats:
    batches: int = 0
    requests: int = 0
    decode_steps: int = 0
    prefill_tokens: int = 0     # padded: rows x the batch's longest prompt
    prompt_tokens: int = 0      # unpadded
    decode_slots: int = 0       # rows x decode executions
    useful_tokens: int = 0      # tokens served
    #: bytes of decode state the last batch carried (its cache pytree's
    #: shapes): the KV cache of an attention model, the SSM and
    #: convolution state of a Mamba-2 one
    state_bytes: int = 0
    wall_s: float = 0.0
    # host perf_counter seconds in the serve.read_tokens, serve.dispatch
    # and serve.model_memory spans
    read_s: float = 0.0
    dispatch_s: float = 0.0
    model_memory_s: float = 0.0
    batch_times: List[BatchTimes] = dataclasses.field(default_factory=list)
    # modeled memory-system latency (FPGA cycles) of the KV access stream
    modeled_p50_cycles: float = 0.0
    modeled_p95_cycles: float = 0.0
    modeled_p99_cycles: float = 0.0
    modeled_makespan_cycles: float = 0.0
    modeled_per_tenant: Dict[int, dict] = dataclasses.field(
        default_factory=dict)
    # per-tenant SLO attainment + cycle-attribution blame (populated
    # only when the server was built with ``slo_cycles``): tenant ->
    # {n, attainment, violations, dominant_blame} where dominant_blame
    # is the attribution component (telemetry.COMPONENTS) contributing
    # the most cycles to that tenant's violating requests.
    modeled_slo_attainment: Dict[int, dict] = dataclasses.field(
        default_factory=dict)


class Server:
    """Batched prefill + lockstep decode with scheduler-based admission."""

    def __init__(self, arch: str, *, smoke: bool = False, mesh=None,
                 sched: SchedulerConfig | None = None,
                 mem: MemoryControllerConfig | None = None,
                 arb_policy: str = "round_robin",
                 arb_weights=None,
                 decode_interval_cycles: int = 64,
                 slo_cycles: float | None = None):
        self.cfg = get_arch(arch, smoke=smoke)
        if self.cfg.family == "encoder":
            raise ValueError("encoder-only architectures do not decode")
        self.lm = build_lm(self.cfg, mesh)
        self.sched = sched or SchedulerConfig(batch_size=8, timeout_cycles=32)
        self.controller = MemoryController(mem or MemoryControllerConfig())
        self.arb_policy = arb_policy
        self.arb_weights = arb_weights
        self.decode_interval_cycles = int(decode_interval_cycles)
        #: modeled per-request sojourn SLO (FPGA cycles). Setting it
        #: turns on lifecycle tracing of the KV replay so the serve
        #: stats carry per-tenant attainment + attribution blame.
        self.slo_cycles = None if slo_cycles is None else float(slo_cycles)
        self.params = self.lm.init(jax.random.key(0))
        self._prefill = jax.jit(
            lambda p, b, ml: self.lm.prefill(p, b, max_len=ml),
            static_argnums=(2,))
        # The cache is donated: each step updates it in place, so a caller
        # must not touch a cache after passing it in.
        self._decode = jax.jit(self.lm.decode_step, donate_argnums=(2,))
        #: batches run so far, across ``serve`` calls: the spans' ``batch``
        self._batches = 0

    def admit(self, requests: List[Request]) -> List[List[Request]]:
        """Scheduler-policy batch formation over the arrival stream."""
        if not requests:
            return []
        batches = form_batches(
            addrs=[r.rid for r in requests],
            rw=[0] * len(requests),
            arrival_cycle=[r.arrival_cycle for r in requests],
            config=self.sched)
        by_id = {r.rid: r for r in requests}
        return [[by_id[int(a)] for a in b.addr] for b in batches]

    def run_batch(self, batch: List[Request], stats: ServeStats) -> None:
        S = max(len(r.prompt) for r in batch)
        prompts = np.stack([np.pad(r.prompt, (S - len(r.prompt), 0))
                            for r in batch])     # left-pad to align ends
        max_new = max(r.max_new_tokens for r in batch)
        max_len = S + max_new + 8
        bid = self._batches
        self._batches += 1
        outs = [[] for _ in batch]
        times = [[] for _ in batch]
        read_s = dispatch_s = 0.0
        with TraceAnnotation("serve.batch", batch=bid):
            t_start = t_first = time.perf_counter()
            with TraceAnnotation("serve.prefill", batch=bid):
                logits, cache, cur = self._prefill(
                    self.params, {"tokens": jnp.asarray(prompts)}, max_len)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            stats.state_bytes = sum(leaf.size * leaf.dtype.itemsize
                                    for leaf in jax.tree.leaves(cache))
            for step in range(max_new):
                with TraceAnnotation("serve.step", batch=bid, step=step):
                    t0 = time.perf_counter()
                    with TraceAnnotation("serve.read_tokens", batch=bid):
                        for i, r in enumerate(batch):
                            if step < r.max_new_tokens:
                                outs[i].append(int(tok[i]))
                                times[i].append(time.perf_counter())
                    t1 = time.perf_counter()
                    with TraceAnnotation("serve.dispatch", batch=bid):
                        logits, cache = self._decode(self.params, tok,
                                                     cache, cur)
                        cur = cur + 1
                        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    t2 = time.perf_counter()
                read_s += t1 - t0
                dispatch_s += t2 - t1
                if step == 0:
                    t_first = t1
            t_end = time.perf_counter()
        for r, o, t in zip(batch, outs, times):
            r.output = o
            r.token_times = t
        stats.batch_times.append(BatchTimes(
            batch=bid, prefill_s=t_first - t_start, decode_s=t_end - t_first,
            read_s=read_s, dispatch_s=dispatch_s))
        stats.prefill_tokens += int(prompts.size)
        stats.prompt_tokens += sum(len(r.prompt) for r in batch)
        stats.decode_steps += max_new
        stats.decode_slots += len(batch) * max_new
        stats.useful_tokens += sum(len(o) for o in outs)
        stats.read_s += read_s
        stats.dispatch_s += dispatch_s
        stats.batches += 1
        stats.requests += len(batch)

    def kv_trace(self, batches: List[List[Request]]):
        """Modeled KV-cache access stream of the batched-decode plan.

        Per batch: prefill appends every prompt token's KV page at the
        admission instant (the batch's last arrival); each lockstep
        decode step ``s`` then appends the new token's page and reads
        the latest context page plus one strided cold page,
        ``decode_interval_cycles`` apart. Requests keep their tenant as
        the controller port, so the stream is exactly what
        ``MemoryController.simulate`` arbitrates between tenants.
        Returns ``(pe_id, rows, rw, arrival_cycle)`` in arrival order.
        """
        pe: List[int] = []
        rows: List[int] = []
        rw: List[int] = []
        arr: List[float] = []

        def emit(r, row, is_write, t):
            pe.append(r.tenant)
            rows.append(row)
            rw.append(is_write)
            arr.append(t)

        for batch in batches:
            base = float(max(r.arrival_cycle for r in batch))
            for r in batch:
                s0 = len(r.prompt)
                kv0 = r.rid * (s0 + r.max_new_tokens + 8)
                for p in range(s0):         # prefill: write prompt KV
                    emit(r, kv0 + p, 1, base)
                for s in range(r.max_new_tokens):
                    t = base + (s + 1) * self.decode_interval_cycles
                    emit(r, kv0 + s0 + s, 1, t)        # append new page
                    emit(r, kv0 + s0 + s - 1, 0, t)    # latest context
                    emit(r, kv0 + (s * 7) % max(1, s0), 0, t)  # cold page
        order = np.argsort(np.asarray(arr, np.float64), kind="stable")
        return (np.asarray(pe, np.int64)[order],
                np.asarray(rows, np.int64)[order],
                np.asarray(rw, np.int32)[order],
                np.asarray(arr, np.float64)[order])

    def model_memory(self, batches: List[List[Request]],
                     stats: ServeStats) -> None:
        """Replay the KV stream through the memory controller's
        open-loop serving pipeline and record modeled latency.

        With ``slo_cycles`` set, the replay runs under a
        :class:`~repro.core.telemetry.TraceRecorder` and each tenant's
        SLO attainment is attributed: violating requests' sojourns are
        decomposed (:class:`~repro.core.telemetry.CycleAttribution`)
        and the dominant component — the answer to "*why* is this
        tenant missing its SLO" (arbitration starvation vs reorder
        slip vs refresh vs replay ...) — lands in the stats.
        """
        t0 = time.perf_counter()
        with TraceAnnotation("serve.model_memory"):
            pe, rows, rw, arr = self.kv_trace(batches)
            if rows.size:
                self._replay(pe, rows, rw, arr, stats)
        stats.model_memory_s += time.perf_counter() - t0

    def _replay(self, pe, rows, rw, arr, stats: ServeStats) -> None:
        trace = None
        if self.slo_cycles is not None:
            from repro.core.telemetry import TraceRecorder
            trace = TraceRecorder()
        res = self.controller.simulate(
            pe, rows, rw, KV_PAGE_BYTES,
            arbiter_policy=self.arb_policy, weights=self.arb_weights,
            arrival_cycle=arr, open_loop=True, trace=trace)
        s = res.serving
        stats.modeled_p50_cycles = s.p50_sojourn
        stats.modeled_p95_cycles = s.p95_sojourn
        stats.modeled_p99_cycles = s.p99_sojourn
        stats.modeled_makespan_cycles = res.makespan_fpga_cycles
        stats.modeled_per_tenant = s.per_port
        if trace is not None:
            from repro.core.telemetry import CycleAttribution
            att = CycleAttribution.from_pipeline(res, trace)
            for p in np.unique(att.pe_id):
                m = att.pe_id == p
                viol = m & (att.sojourn > self.slo_cycles)
                blame = None
                if viol.any():
                    blame = max(
                        ((k, float(v[viol].sum()))
                         for k, v in att.components.items()),
                        key=lambda kv: kv[1])[0]
                stats.modeled_slo_attainment[int(p)] = {
                    "n": int(m.sum()),
                    "violations": int(viol.sum()),
                    "attainment": float(1.0 - viol.sum() / m.sum()),
                    "dominant_blame": blame,
                }

    def serve(self, requests: List[Request]) -> ServeStats:
        stats = ServeStats()
        t0 = time.time()
        with TraceAnnotation("serve"):
            with TraceAnnotation("serve.admit"):
                batches = self.admit(requests)
            for batch in batches:
                self.run_batch(batch, stats)
            self.model_memory(batches, stats)
        stats.wall_s = time.time() - t0
        return stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-34b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--slo-cycles", type=float, default=None,
                    help="modeled sojourn SLO; turns on per-tenant "
                         "attainment attribution")
    args = ap.parse_args()
    use_compile_cache()

    server = Server(args.arch, smoke=args.smoke,
                    slo_cycles=args.slo_cycles)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(
                        0, server.cfg.vocab_size, args.prompt_len
                    ).astype(np.int32),
                    max_new_tokens=args.new_tokens,
                    arrival_cycle=i * 3)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    stats = server.serve(reqs)
    print(f"[serve] {stats.requests} requests in {stats.batches} batches, "
          f"{stats.decode_steps} decode steps, "
          f"{stats.prefill_tokens} prefill tokens "
          f"({100 * stats.prompt_tokens / max(1, stats.prefill_tokens):.1f}% "
          f"prompt, the rest padding), {stats.wall_s:.1f}s")
    print(f"[serve] decode state of the last batch: {stats.state_bytes} "
          f"bytes")
    ttft = [1e3 * (r.token_times[0] - t0) for r in reqs if r.token_times]
    tpot = [1e3 * float(np.mean(np.diff(r.token_times)))
            for r in reqs if len(r.token_times) > 1]
    for name, ms in (("TTFT", ttft), ("TPOT", tpot)):
        if ms:
            p50, p95 = np.percentile(ms, [50, 95])
            print(f"[serve] {name} p50={p50:.1f}ms p95={p95:.1f}ms")
    print(f"[serve] decode slot use "
          f"{100 * stats.useful_tokens / max(1, stats.decode_slots):.1f}% "
          f"({stats.useful_tokens}/{stats.decode_slots}); host token reads "
          f"{stats.read_s:.3f}s, dispatch {stats.dispatch_s:.3f}s, "
          f"modeled-memory replay {stats.model_memory_s:.3f}s")
    if stats.batch_times:
        slow = max(stats.batch_times, key=lambda b: b.prefill_s + b.decode_s)
        print(f"[serve] slowest batch {slow.batch}: prefill to first token "
              f"{slow.prefill_s:.3f}s, decode loop {slow.decode_s:.3f}s "
              f"(token reads {slow.read_s:.3f}s, "
              f"dispatch {slow.dispatch_s:.3f}s)")
    print(f"[serve] modeled KV latency (FPGA cycles): "
          f"p50={stats.modeled_p50_cycles:.1f} "
          f"p95={stats.modeled_p95_cycles:.1f} "
          f"p99={stats.modeled_p99_cycles:.1f}")
    for p, rec in sorted(stats.modeled_slo_attainment.items()):
        print(f"[serve] tenant {p}: SLO attainment "
              f"{100 * rec['attainment']:.1f}% "
              f"({rec['violations']}/{rec['n']} violations, "
              f"blame={rec['dominant_blame']})")
    print(f"[serve] sample output: {reqs[0].output}")


if __name__ == "__main__":
    main()
