"""Sorted-scatter — the scheduler's write-side locality payoff, in Pallas.

Mirror image of ``sorted_gather``: the FPGA scheduler reorders a WRITE
batch so same-row writes reach DRAM back-to-back. The TPU analogue: feed
*sorted* row indices to a scalar-prefetch scatter whose *output* BlockSpec
index map selects the HBM tile holding ``table[idx[i]]`` (the access unit
of ``sorted_gather``). While consecutive grid steps map to the same tile
the Pallas pipeline emitter keeps it in VMEM and defers the VMEM→HBM
copy-out until the tile changes — writes to that tile, duplicate rows
included, are **coalesced in VMEM** and each touched tile is read and
written back once. That is simultaneously the row-buffer-hit economics of
the paper and its weak-consistency rule: within a sorted run the last
writer (in arrival order, preserved by the stable sort) wins.

The table is passed through ``input_output_aliases`` and read in its own
layout, so rows never written keep their original contents and, with the
table donated, the update happens in place — not a rebuild of the table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default
from repro.kernels.sorted_gather.kernel import (pad_requests, place_row,
                                                select_row, tile_rows)


def _scatter_tile_kernel(idx_ref, val_ref, table_ref, out_ref):
    # The output tile stays in VMEM while the sorted stream stays in it:
    # load it from HBM (table_ref) on entry, then keep updating the VMEM
    # copy, so every write of a run lands before the one copy-out.
    i, j = pl.program_id(0), pl.program_id(1)
    s = i * val_ref.shape[0] + j
    t_out = out_ref.shape[0]
    r = idx_ref[s]
    prev = idx_ref[jnp.maximum(s - 1, 0)]

    @pl.when((s == 0) | (prev // t_out != r // t_out))
    def _():
        out_ref[...] = table_ref[...]

    out_ref[...] = place_row(out_ref[...], select_row(val_ref[...], j),
                             r % t_out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_rows(table: jnp.ndarray, sorted_idx: jnp.ndarray,
                 values: jnp.ndarray, *, interpret: bool | None = None):
    """Write ``values[i]`` to ``table[sorted_idx[i]]``, last writer wins.

    Callers must pass indices sorted (stably) by row; *correctness*
    requires it, not only the coalescing: a tile is loaded from HBM when
    the stream enters it, so a stream that left a tile and came back
    would reload it without the writes still waiting in VMEM.
    ``interpret=None`` interprets on the CPU backend only.
    """
    rows, d = table.shape
    t = tile_rows(table.dtype)
    t_tab = min(t, rows)           # a short table is one (whole) block
    idx, vals = pad_requests(sorted_idx.astype(jnp.int32),
                             values.astype(table.dtype), multiple=t)
    tile = lambda i, j, idx_ref: (idx_ref[i * t + j] // t_tab, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(idx.shape[0] // t, t),
        in_specs=[
            pl.BlockSpec((t, d), lambda i, j, idx_ref: (i, 0)),   # values
            pl.BlockSpec((t_tab, d), tile),                       # table
        ],
        out_specs=pl.BlockSpec((t_tab, d), tile),
    )
    return pl.pallas_call(
        _scatter_tile_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), table.dtype),
        input_output_aliases={2: 0},   # table buffer is updated in place
        interpret=interpret_default() if interpret is None else interpret,
    )(idx, vals, table)
