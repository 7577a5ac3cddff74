"""Sorted-gather — the scheduler's locality payoff, in Pallas.

The FPGA scheduler reorders a batch so same-row requests reach DRAM
back-to-back and hit the open row buffer. The TPU analogue: feed *sorted*
row indices to a scalar-prefetch gather whose BlockSpec index map selects
the HBM tile holding ``table[idx[i]]``. The Pallas pipeline emitter skips
the HBM→VMEM copy when consecutive grid steps map to the same block — so
after sorting, requests that fall in an already-open tile cost **zero
additional HBM traffic**, exactly the row-buffer-hit economics of the paper
(and why the wrapper sorts first).

Access unit: one HBM tile, ``tile_rows(dtype)`` table rows by ``d`` (8
rows of 32-bit words; 16 of bf16, which packs two rows per word). The TPU
keeps a 2-D array in such tiles, so one table row is not contiguous in HBM
and the chip cannot fetch it alone; a tile is the smallest block it can
read without relaying out the table. The table is read in place in its
own layout, and the kernel picks each request's row out of the tile in
VMEM. Output rows are assembled ``tile_rows`` at a time, one block per
``tile_rows`` requests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def tile_rows(dtype) -> int:
    """Rows of ``dtype`` in one (8, 128)-word TPU tile."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def select_row(block, r):
    """Row ``r`` (traced) of a VMEM block, as ``(1, d)``.

    The TPU loads and stores sublanes only at static or tile-aligned
    offsets, so the row is chosen by a chain of selects over the block's
    static rows; a select moves bits unchanged (no arithmetic, exact for
    every dtype, -0.0 and NaN included)."""
    row = block[0:1]
    for k in range(1, block.shape[0]):
        row = jnp.where(r == k, block[k:k + 1], row)
    return row


def place_row(block, row, r):
    """``block`` with row ``r`` (traced) replaced by ``row`` ``(1, d)``."""
    sel = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0) == r
    return jnp.where(sel, jnp.broadcast_to(row, block.shape), block)


def pad_requests(idx, *arrays, multiple: int):
    """Pad a sorted request stream to a multiple of ``multiple`` by
    repeating its last request, which keeps it sorted and changes no
    result (a repeated read, or the run's last write once more)."""
    pad = -idx.shape[0] % multiple
    if not pad:
        return (idx, *arrays)
    rep = lambda a: jnp.concatenate([a, jnp.repeat(a[-1:], pad, 0)])
    return (rep(idx), *(rep(a) for a in arrays))


def _gather_tile_kernel(idx_ref, tile_ref, out_ref):
    # The index map already steered the pipeline to the tile holding this
    # request's row; pick the row and place it in the output block.
    i, j = pl.program_id(0), pl.program_id(1)
    r = idx_ref[i * out_ref.shape[0] + j] % tile_ref.shape[0]
    out_ref[...] = place_row(out_ref[...], select_row(tile_ref[...], r), j)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(table: jnp.ndarray, sorted_idx: jnp.ndarray,
                *, interpret: bool | None = None):
    """Gather ``table[sorted_idx]``; callers must pass sorted indices to get
    the dedup/locality behaviour (unsorted input is still correct).
    ``interpret=None`` interprets on the CPU backend only."""
    n = sorted_idx.shape[0]
    rows, d = table.shape
    t = tile_rows(table.dtype)
    t_in = min(t, rows)            # a short table is one (whole) block
    (idx,) = pad_requests(sorted_idx.astype(jnp.int32), multiple=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(idx.shape[0] // t, t),
        in_specs=[pl.BlockSpec(
            (t_in, d), lambda i, j, idx_ref: (idx_ref[i * t + j] // t_in, 0))],
        out_specs=pl.BlockSpec((t, d), lambda i, j, idx_ref: (i, 0)),
    )
    out = pl.pallas_call(
        _gather_tile_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], d), table.dtype),
        interpret=interpret_default() if interpret is None else interpret,
    )(idx, table)
    return out[:n]
