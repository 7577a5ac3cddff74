"""Bitonic sorting network — the scheduler's reordering engine (paper Fig. 2).

TPU adaptation of the FPGA sorting fabric: the FPGA unrolls the network
*spatially* (one comparator per wire pair); the TPU time-multiplexes the
``log2(N)(log2(N)+1)/2`` stages onto the 8x128 VPU lanes, each stage being a
single vectorized compare-exchange over the whole batch held in VMEM. The
stage count of Eq. 1 is preserved exactly; only the per-stage constant
changes (one VPU pass instead of one FPGA cycle).

Layout: a batch of ``N`` is held as ``(N / lanes, lanes)`` with
``lanes = min(N, 128)``, element ``i`` at row ``i // lanes``, lane
``i % lanes``. Its stride-``2^j`` partner ``i ^ 2^j`` is then either in
the same row (``2^j < lanes``) or in the same lane (``2^j >= lanes``),
so every stage is two rotations along one axis plus elementwise
selects — no gathers and no reshapes, which the TPU's vector layout
could not express.

Stability (the consistency-model requirement that same-address requests
keep arrival order) is obtained by comparing ``(key, arrival_id)``
lexicographically; ids are unique, so the network implements a total order
and the result equals a stable sort by key.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _compare_exchange(keys, ids, vals, pos, j_exp: int, k_exp: int, roll):
    """One network stage: element ``i`` meets ``i ^ 2^j_exp``; the pair
    sorts ascending where bit ``k_exp`` of ``i`` is clear."""
    lanes = keys.shape[1]
    j = 1 << j_exp
    axis, step = (1, j) if j < lanes else (0, j // lanes)
    size = keys.shape[axis]
    # One of the two rotations by ``step`` brings each element's partner
    # in; the rotated positions say which, whatever the rotation's sign
    # convention.
    fwd = roll(pos, step, axis) == (pos ^ j)

    def partner(x):
        return jnp.where(fwd, roll(x, step, axis), roll(x, size - step, axis))

    pk, pi, pv = partner(keys), partner(ids), partner(vals)
    gt = (keys > pk) | ((keys == pk) & (ids > pi))   # composite (key, id)
    lower = (pos & j) == 0
    ascending = ((pos >> k_exp) & 1) == 0
    take = gt ^ (lower ^ ascending)   # lower keeps min when ascending
    return (jnp.where(take, pk, keys), jnp.where(take, pi, ids),
            jnp.where(take, pv, vals))


def sort_network(keys, ids, vals, *, roll=jnp.roll):
    """Run the full bitonic network on int32 batches of a power-of-two
    size, laid out ``(rows, lanes)`` as above (1-D input is one row).
    ``roll`` rotates like ``jnp.roll``; kernels pass the TPU's own."""
    shape = keys.shape
    keys, ids, vals = (x.reshape(-1, shape[-1]) for x in (keys, ids, vals))
    rows, lanes = keys.shape
    n = rows * lanes
    assert n & (n - 1) == 0, "bitonic network needs a power-of-two batch"
    pos = (jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1))
    m = n.bit_length() - 1
    for k_exp in range(1, m + 1):
        for j_exp in range(k_exp - 1, -1, -1):
            keys, ids, vals = _compare_exchange(keys, ids, vals, pos,
                                                j_exp, k_exp, roll)
    return keys.reshape(shape), ids.reshape(shape), vals.reshape(shape)


def _sort_kernel(keys_ref, vals_ref, out_keys_ref, out_perm_ref,
                 out_vals_ref):
    """Sort one scheduler batch (a grid row) resident in VMEM."""
    keys = keys_ref[...]
    rows, lanes = keys.shape
    ids = (jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1))
    skeys, sids, svals = sort_network(keys, ids, vals_ref[...],
                                      roll=pltpu.roll)
    out_keys_ref[...] = skeys
    out_perm_ref[...] = sids
    out_vals_ref[...] = svals


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_batched(keys: jnp.ndarray, vals: jnp.ndarray,
                         *, interpret: bool | None = None):
    """Sort each row of ``keys (G, N)`` with payload ``vals``; returns
    (sorted_keys, perm, sorted_vals). N must be a power of two; each grid
    step sorts one batch entirely in VMEM (the scheduler's double-buffered
    queue fits VMEM for every Table-I batch size). ``interpret=None``
    interprets on the CPU backend only."""
    g, n = keys.shape
    lanes = min(n, 128)
    view = (g, n // lanes, lanes)
    blk = pl.BlockSpec((None, n // lanes, lanes), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _sort_kernel,
        grid=(g,),
        in_specs=[blk, blk],
        out_specs=(blk, blk, blk),
        out_shape=(
            jax.ShapeDtypeStruct(view, keys.dtype),
            jax.ShapeDtypeStruct(view, jnp.int32),
            jax.ShapeDtypeStruct(view, vals.dtype),
        ),
        interpret=interpret_default() if interpret is None else interpret,
    )(keys.reshape(view), vals.reshape(view))
    return tuple(x.reshape(g, n) for x in out)
