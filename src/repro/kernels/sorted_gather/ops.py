"""Public sorted-gather op: schedule (sort) → gather → unsort.

``sorted_gather(table, idx)`` is value-identical to ``table[idx]``. The
request stream is stable-sorted by row id (the scheduler), the Pallas
gather streams rows with HBM locality + revisit dedup, and the inverse
permutation restores arrival order (the Fig. 2 read-pointer writeback).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bitonic_sort import ops as bitonic_ops
from repro.kernels.sorted_gather.kernel import gather_rows


def sorted_gather(table: jnp.ndarray, indices: jnp.ndarray,
                  *, use_bitonic: bool = False) -> jnp.ndarray:
    idx = indices.reshape(-1)
    if use_bitonic:
        _, perm = bitonic_ops.sort_with_indices(idx)
    else:
        perm = jnp.argsort(idx, stable=True)
    sorted_idx = jnp.take(idx, perm, axis=0)
    gathered = gather_rows(table, sorted_idx)
    inv_perm = jnp.argsort(perm, stable=True)
    out = jnp.take(gathered, inv_perm, axis=0)
    return out.reshape(*indices.shape, table.shape[-1])
