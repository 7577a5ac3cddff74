"""Unified memory controller — the paper's top-level IP as a JAX module.

``MemoryController`` is the single object models talk to. Like the FPGA IP,
it routes each request class to the right engine:

* single/irregular row requests (embedding rows, KV pages, graph
  adjacency) → **scheduler** (batch → stable sort by row → locality
  gather/scatter → unsort) and optionally the **cache engine**
  (VMEM-resident hot rows, kept write-coherent);
* bulk/streaming requests (weight tiles, activations) → **DMA engine**
  (``bulk_read`` / ``bulk_write``).

Both directions are covered: ``gather``/``cached_gather``/``bulk_read``
on the read side, ``scatter``/``cached_scatter``/``bulk_write`` on the
write side (single-type batches per the paper's weak consistency model).

Every path has identical value semantics to the naive access (``table[idx]``
/ ``table.at[idx].set`` / ``copy``) so engines can be enabled
per-application exactly like the paper's synthesis parameters — disabling
an engine can never change results, only performance. That contract is
property-tested.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import capture as capture_mod
from repro.core import channels as channels_mod
from repro.core import dma_engine, pipeline as pipeline_mod
from repro.core import scatter_util, scheduler
from repro.core.config import MemoryControllerConfig
from repro.core.pipeline import PipelineResult, RequestStream
from repro.core.timing import DRAMTimings, DDR4_2400, SimResult


def sorted_gather(
    table: jnp.ndarray, indices: jnp.ndarray, *, use_pallas: bool = False
) -> jnp.ndarray:
    """Scheduler-path gather: reorder requests by row before touching HBM.

    Equivalent to ``table[indices]``; the sort converts a random HBM access
    stream into a quasi-sequential one (row-buffer/burst locality) and lets
    the kernel serve duplicate rows from VMEM. The stable sort preserves
    same-address arrival order (weak consistency rule).
    """
    idx_flat = indices.reshape(-1)
    if use_pallas:
        from repro.kernels.sorted_gather import ops as sg_ops
        out = sg_ops.sorted_gather(table, idx_flat)
    else:
        _, perm, inv_perm = scheduler.sort_requests(idx_flat)
        gathered = jnp.take(table, jnp.take(idx_flat, perm, axis=0), axis=0)
        out = jnp.take(gathered, inv_perm, axis=0)
    return out.reshape(*indices.shape, table.shape[-1])


def sorted_scatter(
    table: jnp.ndarray, indices: jnp.ndarray, values: jnp.ndarray,
    *, mode: str = "set", use_pallas: bool = False,
) -> jnp.ndarray:
    """Scheduler-path scatter: reorder a WRITE batch by row before HBM.

    Value-identical to the in-order write stream: for ``mode="set"`` the
    stable sort keeps same-address arrival order so the last writer wins
    (weak-consistency rule); for ``mode="add"`` each run accumulates in
    promoted (≥f32) precision and rounds to the table dtype once.
    Duplicate rows are coalesced — each touched HBM tile is written back
    once. Thin
    wrapper over the single sort-and-coalesce pipeline in
    ``repro.kernels.sorted_scatter.ops``.
    """
    from repro.kernels.sorted_scatter import ops as ss_ops
    return ss_ops.sorted_scatter(
        table, indices, values, mode=mode,
        backend="pallas" if use_pallas else "xla")


def scatter_set_last(table: jnp.ndarray, idx: jnp.ndarray,
                     vals: jnp.ndarray) -> jnp.ndarray:
    """Deterministic last-writer-wins scatter without sorting.

    XLA's ``table.at[idx].set`` leaves duplicate-index ordering
    implementation-defined, so the unscheduled path cannot rely on it and
    still honor the engine-toggle value-identity contract. Instead the
    winner of each row is found with a commutative reduction (max of
    arrival stamp), and only winners write; losers target a sacrificial
    padding row.
    """
    n = idx.shape[0]
    stamp = jnp.arange(1, n + 1, dtype=jnp.int32)
    winner = jnp.zeros((table.shape[0],), jnp.int32).at[idx].max(stamp)
    is_winner = jnp.take(winner, idx) == stamp
    return scatter_util.masked_row_set(table, idx, vals, is_winner)


@dataclasses.dataclass
class HotRowCache:
    """Cache-engine integration for jitted models: a pinned hot-row set.

    The LRU cache engine (``cache_engine.py``) mutates state per request —
    correct, but sequential. Inside jitted model code we use the static
    variant the FPGA design also supports for re-usable data structures
    (paper §III: "only the re-usable data structures are globally cached"):
    the ``hot_ids`` rows are pinned in fast memory at build time, lookups
    that hit them never touch HBM. Value-identical to ``table[idx]``.
    """

    hot_ids: jnp.ndarray     # (H,) sorted unique row ids
    hot_data: jnp.ndarray    # (H, d) pinned rows (VMEM-resident working set)

    @classmethod
    def build(cls, table: jnp.ndarray, hot_ids) -> "HotRowCache":
        hot_ids = jnp.sort(jnp.asarray(hot_ids, dtype=jnp.int32))
        return cls(hot_ids=hot_ids, hot_data=jnp.take(table, hot_ids, axis=0))

    def gather(self, table: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
        idx = indices.reshape(-1)
        # Empty hot set: clipping searchsorted positions to [0, H-1] would
        # wrap to -1 and index from the end — there is nothing to hit, so
        # serve everything from memory. (H is static under jit.)
        if self.hot_ids.shape[0] == 0:
            return jnp.take(table, idx, axis=0).reshape(
                *indices.shape, table.shape[-1])
        pos = jnp.searchsorted(self.hot_ids, idx)
        pos = jnp.clip(pos, 0, self.hot_ids.shape[0] - 1)
        hit = self.hot_ids[pos] == idx
        from_cache = jnp.take(self.hot_data, pos, axis=0)
        from_mem = jnp.take(table, idx, axis=0)
        out = jnp.where(hit[:, None], from_cache, from_mem)
        return out.reshape(*indices.shape, table.shape[-1])

    def hit_mask(self, indices: jnp.ndarray) -> jnp.ndarray:
        idx = indices.reshape(-1)
        if self.hot_ids.shape[0] == 0:      # see gather: all-miss, no wrap
            return jnp.zeros(idx.shape, bool)
        pos = jnp.clip(jnp.searchsorted(self.hot_ids, idx), 0,
                       self.hot_ids.shape[0] - 1)
        return self.hot_ids[pos] == idx

    def repin(self, table: jnp.ndarray) -> "HotRowCache":
        """Refresh the pinned rows from an updated table (the
        write-allocate rule for the static hot set): after any write to
        ``table``, re-pinning keeps subsequent cached gathers coherent."""
        return HotRowCache(hot_ids=self.hot_ids,
                           hot_data=jnp.take(table, self.hot_ids, axis=0))


@dataclasses.dataclass
class MemoryController:
    """The configured controller instance handed to models/pipelines."""

    config: MemoryControllerConfig
    use_pallas: bool = False
    timings: DRAMTimings = dataclasses.field(default_factory=lambda: DDR4_2400)
    # Opt-in trace recorder (ARCHITECTURE §13). When set, the data-plane
    # entry points below report their request batches into it — values
    # are never touched (``capture=None`` is bit-identical, the same
    # contract as ``telemetry.TraceRecorder``). The ``mc_*`` model
    # wrappers use the ambient ``capture.active_capture()`` instead (they
    # only hold a config); this field records *only* to itself so a
    # wrapper delegating to a controller method never double-records.
    capture: "capture_mod.TraceCapture | None" = None

    def _record(self, op: str, table, row_ids, rw: int) -> None:
        if self.capture is None:
            return
        n_rows = int(table.shape[0])
        row_bytes = int(table.shape[-1]) * int(
            jnp.dtype(table.dtype).itemsize)
        self.capture.record(op, f"table:{n_rows}x{row_bytes}", n_rows,
                            row_bytes, row_ids, rw=rw)

    def _record_bulk(self, op: str, dst, nbytes: int, rw: int,
                     offset_bytes: int = 0) -> None:
        if self.capture is None:
            return
        total = int(np.prod(dst.shape)) * int(jnp.dtype(dst.dtype).itemsize)
        rb = capture_mod.DEFAULT_ROW_BYTES
        pages = max(1, -(-total // rb))
        first = int(offset_bytes) // rb
        count = max(1, -(-int(nbytes) // rb))
        self.capture.record_slice(op, f"bulk:{pages}x{rb}", pages, rb,
                                  first, min(count, pages - first), rw=rw)

    # --- cache-line / irregular path ---------------------------------------
    def gather(self, table: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
        self._record("gather", table, indices, rw=0)
        if self.config.scheduler.enabled:
            return sorted_gather(table, indices, use_pallas=self.use_pallas)
        return jnp.take(table, indices.reshape(-1), axis=0).reshape(
            *indices.shape, table.shape[-1])

    def cached_gather(
        self, table: jnp.ndarray, indices: jnp.ndarray, cache: HotRowCache
    ) -> jnp.ndarray:
        if self.config.cache.enabled:
            self._record("gather", table, indices, rw=0)
            return cache.gather(table, indices)
        return self.gather(table, indices)

    # --- irregular write path ------------------------------------------------
    def scatter(self, table: jnp.ndarray, indices: jnp.ndarray,
                values: jnp.ndarray, *, mode: str = "set") -> jnp.ndarray:
        """Irregular row writes (embedding-gradient scatter, KV append).

        Value-identical to the in-order write stream whether or not the
        scheduler reorders the batch: ``mode="set"`` resolves duplicate
        rows last-writer-wins; ``mode="add"`` accumulates in promoted
        (≥f32) precision and rounds to the table dtype once — the same
        reference on both paths, so low-precision (bf16) tables don't
        swallow small addends on one path and not the other.
        """
        if mode not in ("set", "add"):
            raise ValueError(f"mode must be 'set' or 'add', got {mode!r}")
        self._record("scatter", table, indices, rw=1)
        if self.config.scheduler.enabled:
            return sorted_scatter(table, indices, values, mode=mode,
                                  use_pallas=self.use_pallas)
        idx = indices.reshape(-1)
        vals = values.reshape(idx.shape[0], table.shape[-1])
        if mode == "add":
            acc = jnp.promote_types(jnp.float32, table.dtype)
            return table.astype(acc).at[idx].add(
                vals.astype(acc)).astype(table.dtype)
        return scatter_set_last(table, idx, vals)

    def cached_scatter(
        self, table: jnp.ndarray, indices: jnp.ndarray,
        values: jnp.ndarray, cache: HotRowCache, *, mode: str = "set",
    ) -> tuple[jnp.ndarray, HotRowCache]:
        """Scatter that keeps a ``HotRowCache`` coherent: the pinned set
        is re-pinned from the updated table (one gather over the hot
        ids). Returns (new_table, new_cache); with the cache engine
        disabled the cache object passes through untouched (and reads
        bypass it, so results are unchanged). The table write itself
        goes through :meth:`scatter`, so the scheduler toggle applies
        independently of the cache toggle."""
        new_table = self.scatter(table, indices, values, mode=mode)
        if self.config.cache.enabled:
            return new_table, cache.repin(new_table)
        return new_table, cache

    # --- bulk path ----------------------------------------------------------
    def bulk_read(self, src: jnp.ndarray) -> jnp.ndarray:
        self._record_bulk(
            "bulk_read", src,
            int(np.prod(src.shape)) * int(jnp.dtype(src.dtype).itemsize),
            rw=0)
        if self.config.dma.enabled:
            return dma_engine.bulk_copy(src, config=self.config.dma,
                                        use_pallas=self.use_pallas)
        return src + 0  # plain copy through the default path

    def bulk_write(self, dst: jnp.ndarray, src: jnp.ndarray,
                   *, offset_elems: int = 0) -> jnp.ndarray:
        """Bulk/streaming write of ``src`` into ``dst`` (weight tiles,
        activation spills, KV page flushes). Value-identical to writing
        the flat region ``[offset, offset+src.size)`` of ``dst``."""
        # Bounds-check on both paths: the default path's
        # dynamic_update_slice would silently clamp, which would make the
        # result depend on the engine toggle.
        if offset_elems < 0 or offset_elems + src.size > dst.size:
            raise ValueError("bulk_write region out of destination bounds")
        item = int(jnp.dtype(dst.dtype).itemsize)
        self._record_bulk("bulk_write", dst, int(src.size) * item, rw=1,
                          offset_bytes=int(offset_elems) * item)
        if self.config.dma.enabled:
            return dma_engine.bulk_write(dst, src, config=self.config.dma,
                                         offset_elems=offset_elems,
                                         use_pallas=self.use_pallas)
        flat = dst.reshape(-1)
        out = jax.lax.dynamic_update_slice(
            flat, src.reshape(-1).astype(dst.dtype), (offset_elems,))
        return out.reshape(dst.shape)

    # --- modeled performance (benchmark substrate) ---------------------------
    # Every modeled number below is produced by the staged pipeline
    # (repro.core.pipeline, ARCHITECTURE §7). ``simulate()`` runs the
    # full composition — arbitration, address mapping, cache filtering,
    # batch scheduling, channel-parallel DRAM service, DMA overlap — and
    # the four ``modeled_*`` entry points are thin wrappers over stage
    # subsets, property-tested bit-identical to their pre-refactor
    # outputs (tests/core/test_pipeline.py).

    def _run(self, stream: RequestStream, *, faults=None, trace=None,
             **stage_kwargs) -> PipelineResult:
        ctx = pipeline_mod.PipelineContext.from_config(self.config,
                                                       self.timings)
        if faults is not None:
            ctx.faults = faults
        ctx.trace = trace
        stages = pipeline_mod.default_stages(ctx, **stage_kwargs)
        return pipeline_mod.run_pipeline(stream, ctx, stages)

    def simulate(
        self, pe_id, row_ids, rw, row_bytes: int,
        *, arbiter_policy: str = "round_robin", weights=None,
        coalesce_writes: bool = False,
        arrival_cycle=None, open_loop: bool | None = None,
        faults=None, trace=None,
    ) -> PipelineResult:
        """Full-pipeline simulation of an irregular row trace — the
        paper's headline composition (cache engine *and* batch scheduler
        *and* multi-channel service together).

        ``pe_id=None`` models a single-port front end (no arbitration);
        otherwise the ``config.num_pes`` per-channel arbiters merge the
        per-PE streams. ``rw=None`` means an all-read trace. Returns a
        :class:`~repro.core.pipeline.PipelineResult` whose per-stage
        breakdown sums to ``makespan_fpga_cycles``; the legacy
        DRAM-only view is ``.as_channel_result()``.

        ``config.dram_sched`` selects each channel interface's DRAM
        *command* scheduler (fifo / frfcfs / frfcfs_cap + refresh,
        ARCHITECTURE §8): the default FIFO window-1 model is
        bit-identical to the pre-PR service stage, pinned by the
        golden-trace suite (``tests/core/test_golden_pipeline.py``).

        ``arrival_cycle`` (per-request FPGA-cycle stamps) switches the
        run to *open-loop serving* (ARCHITECTURE §9): no request is
        granted or issued before it arrives, per-channel idle gaps
        advance the clock, and the result's ``.serving`` reports
        per-request sojourn times with p50/p95/p99 and sustained
        throughput. Serving runs the drop-free stage subset (no cache
        filter, no batch scheduler — both retire the per-request
        identity sojourn accounting needs). With all stamps zero the
        serving datapath is bit-identical to the closed-loop pipeline
        (property-tested); ``open_loop`` forces the mode explicitly.

        ``faults`` overrides ``config.faults`` for this run (RAS layer,
        ARCHITECTURE §10): error injection, ECC/CRC handling, bounded
        replay with backoff, outage windows and graceful degradation —
        the result then carries a ``.fault`` stats block (and, open
        loop, per-request ``.dropped`` flags). ``None`` inherits the
        config; an inactive :class:`~repro.core.config.FaultConfig` is
        bit-identical to no fault layer at all (property-tested).

        ``trace`` (a :class:`~repro.core.telemetry.TraceRecorder`)
        opts into per-request lifecycle tracing (ARCHITECTURE §11):
        every stage emits its events into the recorder — arrivals,
        grants, cache verdicts, batch ids, reorder-window entries,
        per-attempt DRAM issues, replays, completions, plus channel
        timeline events — for the Perfetto exporter
        (``repro.launch.tracing``) and the cycle-attribution report
        (``repro.core.telemetry.CycleAttribution``). ``trace=None``
        leaves every code path bit-identical (property-tested).

        Raises ``ValueError`` on an empty trace — a zero-request
        simulation is almost always an upstream bug (an over-filtered
        trace or a bad selection), so it fails loudly here instead of
        returning an all-zero result that silently poisons derived
        bandwidth/latency numbers. Callers that genuinely want the
        degenerate run can build it from the pipeline primitives
        (``RequestStream.from_rows`` + ``run_pipeline``).
        """
        stream = RequestStream.from_rows(row_ids, rw, row_bytes=row_bytes,
                                         pe_id=pe_id,
                                         arrival_cycle=arrival_cycle)
        if len(stream) == 0:
            raise ValueError(
                "simulate() got an empty trace (0 requests) — refusing "
                "to report an all-zero result; check the upstream trace "
                "generation/filtering (use the pipeline primitives "
                "directly if a degenerate empty run is intended)")
        ports = self.config.num_pes if pe_id is not None else None
        serving = open_loop if open_loop is not None else \
            stream.has_arrivals
        if serving:
            ctx = pipeline_mod.PipelineContext.from_config(self.config,
                                                           self.timings)
            ctx.scheduler = None
            ctx.open_loop = True
            if faults is not None:
                ctx.faults = faults
            ctx.trace = trace
            stages = pipeline_mod.default_stages(
                ctx, ports=ports, arbiter_policy=arbiter_policy,
                weights=weights, cache=False)
            return pipeline_mod.run_pipeline(stream, ctx, stages)
        return self._run(
            stream,
            ports=ports, faults=faults, trace=trace,
            arbiter_policy=arbiter_policy, weights=weights,
            cache=True, coalesce_writes=coalesce_writes)

    def modeled_gather_time(
        self, row_ids: np.ndarray, row_bytes: int
    ) -> SimResult:
        """Modeled DRAM access time for an irregular read-only row trace,
        after the controller's scheduling policy is applied (Fig. 7
        methodology). Pipeline subset: AddressMap → BatchScheduler →
        DRAMService — so a multi-channel config reports the channel
        makespan here too (it used to fall back to single-channel
        numbers); ``num_channels=1`` is bit-identical to the seed
        ``schedule_trace`` + ``simulate_dram_access`` composition."""
        stream = RequestStream.from_rows(row_ids, row_bytes=row_bytes)
        return self._run(stream, cache=False).as_sim_result()

    def modeled_access_time(
        self, row_ids: np.ndarray, rw: np.ndarray, row_bytes: int,
        *, coalesce_writes: bool = False,
    ) -> SimResult:
        """Modeled DRAM time for a mixed read/write row trace: the
        scheduler forms single-type batches and row-sorts each, then the
        stream is costed with open-row state *and* bus-turnaround
        penalties (the Fig. 7 methodology extended to writes).
        ``coalesce_writes`` also models per-batch VMEM write coalescing
        (what the sorted_scatter data plane does; fig7w uses it).

        The trace is first decomposed by the configured
        :class:`~repro.core.channels.AddressMap`; each channel schedules
        and services its share independently, and the returned
        ``total_fpga_cycles`` is the multi-channel *makespan* (slowest
        channel). At ``num_channels=1`` the map is the identity and this
        is exactly the paper's single-interface pipeline (bit-identical:
        ``test_single_channel_matches_plain_simulator``). See
        :meth:`modeled_channel_access_time` for the full per-channel
        breakdown."""
        return self.modeled_channel_access_time(
            row_ids, rw, row_bytes,
            coalesce_writes=coalesce_writes).as_sim_result()

    def modeled_channel_access_time(
        self, row_ids: np.ndarray, rw: np.ndarray, row_bytes: int,
        *, coalesce_writes: bool = False,
    ) -> channels_mod.ChannelSimResult:
        """Multi-channel view of :meth:`modeled_access_time`: the
        configured AddressMap splits the trace, each channel runs its
        own scheduler front end + open-row simulation, and the result
        carries makespan, per-channel occupancy and hit counts.
        Pipeline subset: AddressMap → BatchScheduler → DRAMService."""
        stream = RequestStream.from_rows(row_ids, rw, row_bytes=row_bytes)
        return self._run(
            stream, cache=False,
            coalesce_writes=coalesce_writes).as_channel_result()

    def modeled_multiport_access_time(
        self, pe_id: np.ndarray, row_ids: np.ndarray, rw: np.ndarray,
        row_bytes: int, *, policy: str = "round_robin",
        weights=None, coalesce_writes: bool = False,
    ) -> channels_mod.ChannelSimResult:
        """Modeled completion time when ``config.num_pes`` ports contend
        for the channels: per-PE streams are merged by the per-channel
        arbiters (round_robin / priority / weighted), scheduled, and
        serviced channel-parallel. The result's ``port_stats`` report
        per-port grants, stall slots and Jain fairness. Pipeline subset:
        AddressMap → PortArbiter → BatchScheduler → DRAMService."""
        stream = RequestStream.from_rows(row_ids, rw, row_bytes=row_bytes,
                                         pe_id=pe_id)
        return self._run(
            stream, ports=self.config.num_pes, arbiter_policy=policy,
            weights=weights, cache=False,
            coalesce_writes=coalesce_writes).as_channel_result()
