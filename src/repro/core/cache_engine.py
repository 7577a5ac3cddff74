"""Cache engine — reconfigurable set-associative LRU cache (paper §IV-A).

The FPGA implementation keeps tags/data in URAM and runs two interlocked
pipelines (4-stage PE pipeline for lookups, 3-stage MEM pipeline for fills)
sharing Tag RAM, Data RAM and LRU state. Here the same structure is a
functional state pytree — ``CacheState`` — threaded through a ``lax.scan``:
each scan step is one "pipeline beat" that performs the tag compare, the LRU
update, and (on miss) the MEM-pipeline fill of the victim way. MEM-pipeline
priority (fills stall lookups) is inherent in the sequential scan semantics.

This module is the *oracle* for the `repro.kernels.cache_lookup` Pallas
kernel and the measurement substrate for the Table III / Fig. 7 cases of
``tests/core/test_paper_figures.py``.
Address mapping: line = addr // line_bytes, set = line % num_sets,
tag = line // num_sets.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scatter_util
from repro.core.config import CacheConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CacheState:
    """Tag RAM + Data RAM + LRU age matrix + dirty bits, as arrays.

    ``age`` holds the global access stamp of each way's last touch; LRU
    victim = argmin(age), with invalid ways pinned to age -1 so they are
    always chosen first. ``clock`` is the global stamp counter. ``dirty``
    marks ways whose Data RAM line is newer than DRAM (write-back policy);
    evicting a dirty way emits a victim write-back to the backing store.
    """

    tags: jnp.ndarray    # (sets, ways) int32
    valid: jnp.ndarray   # (sets, ways) bool
    age: jnp.ndarray     # (sets, ways) int32
    data: jnp.ndarray    # (sets, ways, line_elems) — cached lines
    clock: jnp.ndarray   # () int32
    dirty: jnp.ndarray   # (sets, ways) bool


def init_cache(
    config: CacheConfig, line_elems: int, dtype=jnp.float32
) -> CacheState:
    sets, ways = config.num_sets, config.associativity
    return CacheState(
        tags=jnp.zeros((sets, ways), jnp.int32),
        valid=jnp.zeros((sets, ways), bool),
        age=jnp.full((sets, ways), -1, jnp.int32),
        data=jnp.zeros((sets, ways, line_elems), dtype),
        clock=jnp.zeros((), jnp.int32),
        dirty=jnp.zeros((sets, ways), bool),
    )


def _split_addr(line_id: jnp.ndarray, num_sets: int):
    return line_id % num_sets, line_id // num_sets   # (set, tag)


def lookup(
    state: CacheState, line_id: jnp.ndarray, fill_line: jnp.ndarray,
) -> Tuple[CacheState, jnp.ndarray, jnp.ndarray]:
    """One *read-only* cache beat: probe ``line_id``; on miss install
    ``fill_line``.

    Returns (new_state, hit?, line_data). ``fill_line`` is the line the MEM
    pipeline would return from DRAM; on a hit it is ignored — the Data RAM
    copy is served (so a stale fill cannot clobber a dirty line).

    This beat has no write-back port: a miss that evicts a *dirty* way
    would lose the dirty line. Only feed it states with no dirty lines
    (pure read service) — mixed read/write traces go through
    :func:`access_rw` / :func:`simulate_trace_rw`, or :func:`flush` the
    state first.
    """
    num_sets = state.tags.shape[0]
    set_idx, tag = _split_addr(line_id, num_sets)

    way_tags = state.tags[set_idx]            # (ways,)
    way_valid = state.valid[set_idx]
    match = way_valid & (way_tags == tag)
    hit = jnp.any(match)
    hit_way = jnp.argmax(match)               # valid only when hit

    victim = jnp.argmin(state.age[set_idx])   # LRU (invalid age=-1 wins)
    way = jnp.where(hit, hit_way, victim)

    line_out = jnp.where(hit, state.data[set_idx, way], fill_line)

    clock = state.clock + 1
    new_state = CacheState(
        tags=state.tags.at[set_idx, way].set(tag),
        valid=state.valid.at[set_idx, way].set(True),
        age=state.age.at[set_idx, way].set(clock),
        data=state.data.at[set_idx, way].set(line_out),
        clock=clock,
        # read beat: a hit keeps the way's dirty bit (served from Data RAM),
        # a miss installs a fresh-from-DRAM line, which is clean.
        dirty=state.dirty.at[set_idx, way].set(
            hit & state.dirty[set_idx, way]),
    )
    return new_state, hit, line_out


def simulate_trace_seq(
    state: CacheState, line_ids: jnp.ndarray, table: jnp.ndarray,
) -> Tuple[CacheState, jnp.ndarray, jnp.ndarray]:
    """Reference implementation of :func:`simulate_trace`: one
    ``lax.scan`` beat per request, exactly the paper's shared-pipeline
    stall semantics. O(N) sequential steps — kept as the oracle the
    set-parallel engine is property-tested against, and as the fallback
    for traced inputs / pathologically set-skewed traces."""

    def step(st, lid):
        new_st, hit, line = lookup(st, lid, table[lid])
        return new_st, (hit, line)

    final, (hits, lines) = jax.lax.scan(step, state, line_ids)
    return final, hits, lines


def simulate_trace(
    state: CacheState, line_ids: jnp.ndarray, table: jnp.ndarray,
) -> Tuple[CacheState, jnp.ndarray, jnp.ndarray]:
    """Service a *read* trace through the cache against backing ``table``.

    ``table[line_id]`` plays DRAM. Returns (final_state, hits (N,) bool,
    lines (N, line_elems)). Like :func:`lookup`, this path has no
    write-back port — flush dirty state first, or use
    :func:`simulate_trace_rw` for mixed traces.

    The input chooses the execution strategy, never the semantics (the
    two are bit-identical, see ``trace_engine``): the set-parallel
    engine when the trace is concrete, long enough, and the starting
    state is dirty-free (this path's no-write-back-port contract); the
    one-beat-per-request scan :func:`simulate_trace_seq` otherwise.
    """
    from repro.core import trace_engine

    if trace_engine.auto_parallel_ok(state, line_ids, table=table):
        return trace_engine.simulate_trace_parallel(state, line_ids, table)
    return simulate_trace_seq(state, line_ids, table)


# ---------------------------------------------------------------------------
# Write path (write-allocate; write-back or write-through per CacheConfig)
# ---------------------------------------------------------------------------

def _line_of(tag: jnp.ndarray, set_idx: jnp.ndarray, num_sets: int):
    return tag * num_sets + set_idx


def access_rw(
    state: CacheState,
    table: jnp.ndarray,
    line_id: jnp.ndarray,
    is_write: jnp.ndarray,
    write_line: jnp.ndarray,
    *,
    write_back: bool = True,
) -> Tuple[CacheState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One cache beat of a mixed read/write stream against backing ``table``.

    Write-allocate both ways; full-line writes (the controller's FLIT
    payload is one line). Under write-back a write only touches Data RAM
    and sets the dirty bit; DRAM sees the line when the way is evicted
    (victim flush — the MEM pipeline's write port). Under write-through
    every write also lands in ``table`` immediately and lines stay clean.

    Returns (new_state, new_table, hit?, line_out) where ``line_out`` is
    the value a read observes (reads see earlier writes — the same-address
    ordering the weak-consistency rule guarantees).
    """
    num_sets = state.tags.shape[0]
    n_rows = table.shape[0]
    set_idx, tag = _split_addr(line_id, num_sets)

    way_tags = state.tags[set_idx]
    way_valid = state.valid[set_idx]
    match = way_valid & (way_tags == tag)
    hit = jnp.any(match)
    hit_way = jnp.argmax(match)

    victim = jnp.argmin(state.age[set_idx])
    way = jnp.where(hit, hit_way, victim)

    # Victim write-back: on a miss that evicts a valid dirty way, its line
    # returns to DRAM before the fill (same set, different tag — the victim
    # line can never equal ``line_id``).
    victim_line = jnp.clip(
        _line_of(state.tags[set_idx, way], set_idx, num_sets), 0, n_rows - 1)
    evict = (~hit) & state.valid[set_idx, way] & state.dirty[set_idx, way]
    table = table.at[victim_line].set(
        jnp.where(evict, state.data[set_idx, way], table[victim_line]))

    fill = table[line_id]
    cached = jnp.where(hit, state.data[set_idx, way], fill)
    line_out = jnp.where(is_write, write_line, cached)
    new_dirty_bit = is_write if write_back else jnp.zeros((), bool)
    keep_dirty = hit & state.dirty[set_idx, way] & ~is_write

    if not write_back:
        table = table.at[line_id].set(
            jnp.where(is_write, write_line, table[line_id]))

    clock = state.clock + 1
    new_state = CacheState(
        tags=state.tags.at[set_idx, way].set(tag),
        valid=state.valid.at[set_idx, way].set(True),
        age=state.age.at[set_idx, way].set(clock),
        data=state.data.at[set_idx, way].set(line_out),
        clock=clock,
        dirty=state.dirty.at[set_idx, way].set(new_dirty_bit | keep_dirty),
    )
    return new_state, table, hit, line_out


def simulate_trace_rw_seq(
    state: CacheState,
    line_ids: jnp.ndarray,
    rw: jnp.ndarray,
    write_lines: jnp.ndarray,
    table: jnp.ndarray,
    *,
    config: CacheConfig,
) -> Tuple[CacheState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Reference implementation of :func:`simulate_trace_rw`: strict
    one-beat-at-a-time ``lax.scan`` over :func:`access_rw`. Kept as the
    oracle for the set-parallel engine and as the fallback path."""
    wb = config.write_policy == "write_back"

    def step(carry, req):
        st, tbl = carry
        lid, is_w, wline = req
        st, tbl, hit, line = access_rw(st, tbl, lid, is_w != 0, wline,
                                       write_back=wb)
        return (st, tbl), (hit, line)

    (final, table), (hits, lines) = jax.lax.scan(
        step, (state, table), (line_ids, rw, write_lines))
    return final, table, hits, lines


def simulate_trace_rw(
    state: CacheState,
    line_ids: jnp.ndarray,
    rw: jnp.ndarray,
    write_lines: jnp.ndarray,
    table: jnp.ndarray,
    *,
    config: CacheConfig,
) -> Tuple[CacheState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Service a mixed read/write trace through the cache.

    ``rw[i]`` is 0 (read) / 1 (write); ``write_lines[i]`` is the payload of
    request i (ignored for reads). Returns (final_state, table', hits,
    lines) — call :func:`flush` on the final state to push residual dirty
    lines so ``table'`` matches the naive in-order write stream.

    The input chooses the execution strategy only; results are
    bit-identical (see ``trace_engine``). The set-parallel engine runs
    when, besides :func:`simulate_trace`'s conditions, every line id
    falls inside the table (``0 <= lid < table.shape[0]``) and the
    table/data/payload dtypes agree, so its vectorized value
    reconstruction is exact; otherwise :func:`simulate_trace_rw_seq`.
    """
    from repro.core import trace_engine

    wb = config.write_policy == "write_back"
    if trace_engine.auto_parallel_ok(state, line_ids, rw=rw,
                                     write_lines=write_lines, table=table,
                                     rw_path=True):
        return trace_engine.simulate_trace_rw_parallel(
            state, line_ids, rw, write_lines, table, write_back=wb)
    return simulate_trace_rw_seq(state, line_ids, rw, write_lines, table,
                                 config=config)


def flush(state: CacheState, table: jnp.ndarray
          ) -> Tuple[CacheState, jnp.ndarray]:
    """Write every valid dirty line back to ``table``; clear dirty bits.

    Distinct (set, tag) pairs map to distinct lines, so the scatter has
    no duplicate targets among flushed ways; everything else is masked
    out of the write.
    """
    sets, ways = state.tags.shape
    set_grid = jnp.arange(sets, dtype=state.tags.dtype)[:, None]
    lines = _line_of(state.tags, jnp.broadcast_to(set_grid, (sets, ways)),
                     sets)
    mask = state.valid & state.dirty
    new_table = scatter_util.masked_row_set(
        table, jnp.clip(lines, 0, table.shape[0] - 1).reshape(-1),
        state.data.reshape(sets * ways, -1), mask.reshape(-1))
    return dataclasses.replace(
        state, dirty=jnp.zeros_like(state.dirty)), new_table


@dataclasses.dataclass
class FilterResult:
    """Outcome of running a line-id trace through the cache *filter* —
    the pipeline-stage view of the cache engine (no data movement).

    ``hits[i]`` — request i hit in the cache. ``keep[i]`` — request i is
    forwarded to the DRAM stream (misses always; write hits only under
    write-through). ``wb_pos``/``wb_line`` — victim write-backs emitted
    by evictions of dirty lines: a WRITE of line ``wb_line[j]`` enters
    the DRAM stream immediately *before* the evicting miss at trace
    position ``wb_pos[j]`` (write-back policy only; at most one per
    miss). Residual dirty lines at end of trace are *not* flushed — the
    filter models steady-state occupancy, not teardown.
    """

    hits: np.ndarray      # (N,) bool
    keep: np.ndarray      # (N,) bool
    wb_pos: np.ndarray    # (W,) int64, ascending
    wb_line: np.ndarray   # (W,) int64

    @property
    def hit_rate(self) -> float:
        return float(self.hits.mean()) if self.hits.size else 0.0

    @property
    def n_writebacks(self) -> int:
        return int(self.wb_pos.shape[0])


def _empty_filter_result(n: int) -> FilterResult:
    return FilterResult(hits=np.zeros(n, bool), keep=np.ones(n, bool),
                        wb_pos=np.empty(0, np.int64),
                        wb_line=np.empty(0, np.int64))


#: once at most this many sets still have pending beats, the lockstep
#: walk hands their residual (serial hot-set) subtraces to the dict
#: walk — below ~32 live rows the fixed per-iteration numpy dispatch
#: cost exceeds the ~1µs/beat of the dict.
TAIL_SETS = 32
#: below this trace length the dict walk is trivially fast and the
#: sort/pad setup of the lockstep path is not worth paying.
MIN_LOCKSTEP_TRACE = 4096


class _CompactLayout:
    """Skew-compacted set-parallel layout shared by the numpy lockstep
    walks (:func:`hit_rate_oracle`, :func:`filter_trace_rw`).

    Sets are ordered by descending beat count, so at lockstep depth
    ``j`` the live sets are exactly the prefix ``[:k_js[j]]`` — columns
    are contiguous slices instead of boolean-masked full-width rows, and
    total lockstep work is ``Σ_s min(count_s, d_cut)`` instead of
    ``depth · sets``. Depth is cut at ``d_cut``, the beat count of the
    (``TAIL_SETS``+1)-th hottest set: beyond it at most ``TAIL_SETS``
    serial chains survive, and those residual subtraces (``tail_slices``)
    go to the per-set dict walk, seeded from the lockstep arrays.
    """

    def __init__(self, lids: np.ndarray, sets: int):
        n = lids.shape[0]
        self.set_idx = lids % sets
        self.tag = lids // sets
        self.counts = np.bincount(self.set_idx, minlength=sets)
        counts_d = np.sort(self.counts)[::-1]
        self.d_cut = int(counts_d[TAIL_SETS]) if sets > TAIL_SETS else 0
        self.vec_beats = int(np.minimum(self.counts, self.d_cut).sum())
        self.n = n

    @property
    def worthwhile(self) -> bool:
        """Enough lockstep-coverable work to beat the dict walk (the
        dict tail runs at seq speed, so the combined path only loses
        when setup overhead dominates — i.e. when almost everything is
        tail anyway)."""
        return (self.n >= MIN_LOCKSTEP_TRACE
                and self.vec_beats >= self.n // 4)

    def build(self):
        """Materialize the padded ``(K, d_cut)`` layout (cost O(n +
        K·d_cut); only call when :attr:`worthwhile`)."""
        sets = self.counts.shape[0]
        perm = np.argsort(self.set_idx, kind="stable")
        starts = np.zeros(sets + 1, np.int64)
        np.cumsum(self.counts, out=starts[1:])
        sorder = np.argsort(-self.counts, kind="stable")
        counts_d = self.counts[sorder]
        self.K = K = int(np.searchsorted(-counts_d, 0, side="left"))
        self.sorder = sorder
        cap = np.minimum(counts_d[:K], self.d_cut)
        mask = np.arange(self.d_cut)[None, :] < cap[:, None]
        self.perm2 = np.concatenate(
            [perm[starts[s]:starts[s] + c]
             for s, c in zip(sorder[:K].tolist(), cap.tolist())]) \
            if K else np.empty(0, np.int64)
        self.mask = mask
        # live-prefix length per lockstep depth: #{counts_d > j}
        self.k_js = np.searchsorted(-counts_d[:K], -np.arange(self.d_cut),
                                    side="left")
        # residual serial chains: (row i, set s, global slice) triples
        n_tail = int(np.searchsorted(-counts_d, -self.d_cut, side="left"))
        self.tail_slices = [
            (i, int(sorder[i]),
             perm[starts[sorder[i]] + self.d_cut:
                  starts[sorder[i]] + counts_d[i]])
            for i in range(n_tail)]

    def pad(self, vals: np.ndarray, dtype) -> np.ndarray:
        out = np.zeros((self.K, self.d_cut), dtype)
        out[self.mask] = vals[self.perm2]
        return out


def filter_trace_rw_seq(
    config: CacheConfig, line_ids: np.ndarray, rw: np.ndarray | None = None,
) -> FilterResult:
    """Reference implementation of :func:`filter_trace_rw` — one python
    dict per set, one iteration per request (the :func:`hit_rate_oracle_seq`
    walk extended with dirty bits and victim write-backs). Kept as the
    oracle the lockstep version is property-tested against."""
    sets, ways = config.num_sets, config.associativity
    wb = config.write_policy == "write_back"
    lids = np.asarray(line_ids, dtype=np.int64).ravel()
    rw_arr = np.zeros(lids.shape[0], np.int32) if rw is None \
        else np.asarray(rw, dtype=np.int32).ravel()
    res = _empty_filter_result(lids.shape[0])
    wb_pos: list[int] = []
    wb_line: list[int] = []
    entries: list[dict[int, list]] = [dict() for _ in range(sets)]
    for i, lid in enumerate(lids):
        s, t = int(lid % sets), int(lid // sets)
        e = entries[s]
        w = int(rw_arr[i]) == 1
        if t in e:
            res.hits[i] = True
            rec = e[t]
            rec[0] = i
            if w:
                rec[1] = wb           # write hit: dirty under write-back,
                res.keep[i] = not wb  # forwarded under write-through
            else:
                res.keep[i] = False   # read hit served from Data RAM
        else:
            if len(e) >= ways:
                vt = min(e, key=lambda k: e[k][0])
                if e[vt][1]:
                    wb_pos.append(i)
                    wb_line.append(vt * sets + s)
                del e[vt]
            e[t] = [i, w and wb]      # write-allocate; full-line FLIT
    res.wb_pos = np.asarray(wb_pos, np.int64)
    res.wb_line = np.asarray(wb_line, np.int64)
    return res


def filter_trace_rw(
    config: CacheConfig, line_ids: np.ndarray, rw: np.ndarray | None = None,
) -> FilterResult:
    """Cache filter for the staged pipeline: classify a mixed read/write
    line trace, *remove* requests the cache absorbs, and emit the victim
    write-backs the write-back policy adds to the DRAM stream.

    Semantics (identical to :func:`filter_trace_rw_seq`, property-tested):
    read hits are served on-chip and dropped from the stream; write hits
    are absorbed (dirty) under ``write_back`` and forwarded under
    ``write_through``; misses always go downstream (write-allocate — a
    full-line write needs no fill read); evicting a dirty way inserts a
    WRITE of the victim line just before the evicting miss.

    Vectorized exactly like :func:`hit_rate_oracle` — the skew-compacted
    lockstep walk (:class:`_CompactLayout`): sets advance ordered by
    descending beat count so each depth step touches only the contiguous
    live prefix, with ``(K, ways)`` tag/age/dirty arrays; global arrival
    indices keep LRU victims identical to the dict walk, and the few
    residual serial hot-set chains finish in the dict walk seeded from
    the lockstep state. Tiny or chain-dominated traces dispatch to the
    sequential oracle.
    """
    lids = np.asarray(line_ids, dtype=np.int64).ravel()
    lay = _CompactLayout(lids, config.num_sets)
    if not lay.worthwhile:                        # skewed/tiny: dict wins
        return filter_trace_rw_seq(config, lids, rw)
    return _filter_lockstep(config, lids, rw, lay)


def filter_trace_rw_parallel(
    config: CacheConfig, line_ids: np.ndarray, rw: np.ndarray | None = None,
) -> FilterResult:
    """The lockstep walk of :func:`filter_trace_rw` on any trace, however
    small or skewed — the path the property tests hold bit-identical to
    :func:`filter_trace_rw_seq`."""
    lids = np.asarray(line_ids, dtype=np.int64).ravel()
    if lids.shape[0] == 0:
        return _empty_filter_result(0)
    return _filter_lockstep(config, lids, rw,
                            _CompactLayout(lids, config.num_sets))


def _filter_lockstep(config: CacheConfig, lids: np.ndarray,
                     rw: np.ndarray | None,
                     lay: _CompactLayout) -> FilterResult:
    sets, ways = config.num_sets, config.associativity
    wb = config.write_policy == "write_back"
    n = lids.shape[0]
    rw_arr = np.zeros(n, np.int32) if rw is None \
        else np.asarray(rw, dtype=np.int32).ravel()
    lay.build()
    K = lay.K
    tag_pad = lay.pad(lay.tag, np.int64)
    idx_pad = lay.pad(np.arange(n, dtype=np.int64), np.int64)
    w_pad = lay.pad(rw_arr == 1, bool)
    set_of_row = lay.sorder[:K].astype(np.int64)

    tags_arr = np.zeros((K, ways), np.int64)
    valid = np.zeros((K, ways), bool)
    age = np.full((K, ways), -1, np.int64)
    dirty = np.zeros((K, ways), bool)
    res = _empty_filter_result(n)
    wb_pos_parts: list[np.ndarray] = []
    wb_line_parts: list[np.ndarray] = []
    rows = np.arange(K)
    for j in range(lay.d_cut):
        k = int(lay.k_js[j])          # live prefix: sets with count > j
        t = tag_pad[:k, j]
        match = valid[:k] & (tags_arr[:k] == t[:, None])
        hit = match.any(axis=1)
        way = np.where(hit, match.argmax(axis=1), age[:k].argmin(axis=1))
        r = rows[:k]
        evict = ~hit & valid[r, way] & dirty[r, way]
        if evict.any():
            es = np.flatnonzero(evict)
            wb_pos_parts.append(idx_pad[es, j])
            wb_line_parts.append(tags_arr[es, way[es]] * sets
                                 + set_of_row[es])
        gi = idx_pad[:k, j]
        wl = w_pad[:k, j]
        old_dirty = dirty[r, way]
        tags_arr[r, way] = t
        valid[r, way] = True
        age[r, way] = gi
        dirty[r, way] = np.where(hit, np.where(wl, wb, old_dirty),
                                 wl & wb)
        res.hits[gi] = hit
        res.keep[gi] = ~hit | (wl & (not wb))
    tag_l = lay.tag
    wb_pos_tail: list[int] = []
    wb_line_tail: list[int] = []
    for i, s, sl in lay.tail_slices:
        e = {int(tags_arr[i, w]): [int(age[i, w]), bool(dirty[i, w])]
             for w in range(ways) if valid[i, w]}
        for g, t, is_w in zip(sl.tolist(), tag_l[sl].tolist(),
                              (rw_arr[sl] == 1).tolist()):
            if t in e:
                res.hits[g] = True
                rec = e[t]
                rec[0] = g
                if is_w:
                    rec[1] = wb
                    res.keep[g] = not wb
                else:
                    res.keep[g] = False
            else:
                if len(e) >= ways:
                    vt = min(e, key=lambda kk: e[kk][0])
                    if e[vt][1]:
                        wb_pos_tail.append(g)
                        wb_line_tail.append(vt * sets + s)
                    del e[vt]
                e[t] = [g, is_w and wb]
    if wb_pos_tail:
        wb_pos_parts.append(np.asarray(wb_pos_tail, np.int64))
        wb_line_parts.append(np.asarray(wb_line_tail, np.int64))
    if wb_pos_parts:
        pos = np.concatenate(wb_pos_parts)
        line = np.concatenate(wb_line_parts)
        order = np.argsort(pos, kind="stable")   # one eviction per miss
        res.wb_pos, res.wb_line = pos[order], line[order]
    return res


def hit_rate_oracle_seq(
    config: CacheConfig, line_ids: np.ndarray
) -> Tuple[np.ndarray, float]:
    """Reference implementation of :func:`hit_rate_oracle` — one python
    dict per set, one iteration per request. Kept as the independent
    oracle the vectorized version is property-tested against."""
    sets, ways = config.num_sets, config.associativity
    tags = [dict() for _ in range(sets)]      # set -> {tag: last_use}
    hits = np.zeros(line_ids.shape[0], dtype=bool)
    for i, lid in enumerate(np.asarray(line_ids, dtype=np.int64)):
        s, t = int(lid % sets), int(lid // sets)
        entry = tags[s]
        if t in entry:
            hits[i] = True
        elif len(entry) >= ways:
            del entry[min(entry, key=entry.get)]
        entry[t] = i
    return hits, float(hits.mean()) if hits.size else 0.0


def hit_rate_oracle(
    config: CacheConfig, line_ids: np.ndarray
) -> Tuple[np.ndarray, float]:
    """Fast numpy LRU-cache reference (no data movement) — hit mask + rate.

    Used where only the hit/miss classification feeds the timing model
    (Eq. 2, the Fig. 7 cases) and by hypothesis tests as an independent
    oracle.

    Set-parallel vectorization: all sets advance in lockstep over their
    per-set subtraces (padded to the longest), with numpy ``(sets, ways)``
    tag/age arrays replacing the per-set python dicts — ``max_per_set``
    python iterations instead of N. Ages are global arrival indices
    (unique), so LRU victims are identical to the sequential dict walk.

    The lockstep walk is *skew-compacted* (:class:`_CompactLayout`):
    sets advance ordered by descending beat count so each depth step
    touches only the contiguous prefix of still-live sets, and once at
    most ``TAIL_SETS`` serial hot-set chains remain their residual beats
    fall through to the dict walk seeded from the lockstep state — total
    cost is O(n) array work plus dict-speed tails, so the parallel path
    never loses to the sequential oracle beyond setup noise. Traces
    where almost everything is one serial chain (or tiny ones) dispatch
    straight to the identical sequential oracle.
    """
    sets, ways = config.num_sets, config.associativity
    lids = np.asarray(line_ids, dtype=np.int64).ravel()
    n = lids.shape[0]
    hits = np.zeros(n, dtype=bool)
    if n == 0:
        return hits, 0.0
    lay = _CompactLayout(lids, sets)
    if not lay.worthwhile:             # skewed / tiny: dict walk is faster
        return hit_rate_oracle_seq(config, lids)
    lay.build()
    K = lay.K
    tag_pad = lay.pad(lay.tag, np.int64)
    idx_pad = lay.pad(np.arange(n, dtype=np.int64), np.int64)

    tags_arr = np.zeros((K, ways), np.int64)
    valid = np.zeros((K, ways), bool)
    age = np.full((K, ways), -1, np.int64)   # empty ways always win LRU
    rows = np.arange(K)
    for j in range(lay.d_cut):
        k = int(lay.k_js[j])          # live prefix: sets with count > j
        t = tag_pad[:k, j]
        match = valid[:k] & (tags_arr[:k] == t[:, None])
        hit = match.any(axis=1)
        way = np.where(hit, match.argmax(axis=1), age[:k].argmin(axis=1))
        r = rows[:k]
        gi = idx_pad[:k, j]
        tags_arr[r, way] = t
        valid[r, way] = True
        age[r, way] = gi
        hits[gi] = hit
    tag_l = lay.tag
    for i, _s, sl in lay.tail_slices:
        entry = {int(tags_arr[i, w]): int(age[i, w])
                 for w in range(ways) if valid[i, w]}
        for g, t in zip(sl.tolist(), tag_l[sl].tolist()):
            if t in entry:
                hits[g] = True
            elif len(entry) >= ways:
                del entry[min(entry, key=entry.get)]
            entry[t] = g
    return hits, float(hits.mean())
