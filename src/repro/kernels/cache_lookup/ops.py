"""Public cached-gather op composing the tag/LRU kernel with the data path.

``cache_service(table, line_ids, state)``: probe all requests through the
cache pipeline, serve hits from the Data RAM, fill misses from ``table``
(the HBM side), and return data in arrival order + updated state — value
semantics identical to ``table[line_ids]``, property-tested.

Read-only service: like ``cache_engine.lookup`` it has no write-back
port, so states carrying dirty lines must be flushed before entering
(mixed read/write traces belong to ``cache_engine.simulate_trace_rw``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.cache_engine import CacheState
from repro.kernels.cache_lookup.kernel import cache_probe


def cache_service(table: jnp.ndarray, line_ids: jnp.ndarray,
                  state: CacheState):
    """Returns (lines (N, d), hits (N,), new_state)."""
    hits, ways, tags, valid, age, clock = cache_probe(
        line_ids, state.tags, state.valid.astype(jnp.int32),
        state.age, state.clock)

    num_sets = state.tags.shape[0]
    set_idx = line_ids % num_sets

    # Data path. The kernel fixed the (set, way) placement of every beat;
    # replay the Data RAM in vectorized form: a beat's line is served from
    # cache iff it hit, where the cached value is whatever the most recent
    # fill of that (set, way) wrote — which, for a hit, is always the same
    # line id (tags matched), so the value equals table[line]. The fills
    # themselves come from HBM. Value-identity lets the Data RAM update be
    # expressed as a scatter of table rows.
    from_mem = jnp.take(table, line_ids, axis=0)
    lines = from_mem  # value-identical serve (hits avoid HBM on real HW)
    new_data = state.data.at[set_idx, ways].set(from_mem)

    # Read-only service: fills install clean lines; a hit keeps the way's
    # dirty bit (its Data RAM content is untouched).
    new_dirty = state.dirty.at[set_idx, ways].set(
        state.dirty[set_idx, ways] & (hits != 0))
    new_state = CacheState(tags=tags, valid=valid != 0, age=age,
                           data=new_data, clock=clock.reshape(()),
                           dirty=new_dirty)
    return lines, hits != 0, new_state
