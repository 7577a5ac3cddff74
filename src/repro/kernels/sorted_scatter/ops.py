"""Public sorted-scatter op: schedule (sort) → coalesce → scatter.

``sorted_scatter(table, idx, vals)`` is value-identical to the sequential
write stream ``for i: table[idx[i]] = vals[i]`` (``mode="set"``, last
writer wins) or ``table[idx[i]] += vals[i]`` (``mode="add"``, gradient
accumulation). The request stream is stable-sorted by row id (the
scheduler's WRITE batch reorder), duplicate-row writes are coalesced —
``add`` folds each run into a single row update via a within-run prefix
sum, ``set`` relies on VMEM overwrite inside the kernel — and the Pallas
scatter writes each touched HBM tile back once.

No unsort step is needed on the write path: writes return no payload, so
arrival order only matters *per address*, which the stable sort preserves
(the weak-consistency rule extended to writes).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.scatter_util import masked_row_set
from repro.kernels.bitonic_sort import ops as bitonic_ops
from repro.kernels.sorted_scatter.coalesce import coalesce_add_runs
from repro.kernels.sorted_scatter.kernel import scatter_rows


def sorted_scatter(table: jnp.ndarray, indices: jnp.ndarray,
                   values: jnp.ndarray, *, mode: str = "set",
                   use_bitonic: bool = False,
                   backend: str = "pallas") -> jnp.ndarray:
    """One sort-and-coalesce pipeline for both data planes: the Pallas
    kernel (``backend="pallas"``) and the XLA fallback the controller
    uses off-TPU (``backend="xla"``, last-of-run rows via masked
    scatter). Keeping a single copy is what guarantees the two paths
    cannot drift in batch semantics."""
    if mode not in ("set", "add"):
        raise ValueError(f"mode must be 'set' or 'add', got {mode!r}")
    idx = indices.reshape(-1)
    vals = values.reshape(idx.shape[0], table.shape[-1])
    if use_bitonic:
        _, perm = bitonic_ops.sort_with_indices(idx)
    else:
        perm = jnp.argsort(idx, stable=True)
    sidx = jnp.take(idx, perm, axis=0)
    svals = jnp.take(vals, perm, axis=0)
    if mode == "add":
        # The last slot of each equal-index run — the only one whose VMEM
        # block is flushed — holds table[row] + Σ(run values).
        svals = coalesce_add_runs(table, sidx, svals)
    if backend == "pallas":
        return scatter_rows(table, sidx, svals)
    n = sidx.shape[0]
    is_last = jnp.concatenate(
        [sidx[1:] != sidx[:-1], jnp.ones((1,), bool)]) if n else \
        jnp.zeros((0,), bool)
    return masked_row_set(table, sidx, svals, is_last)
