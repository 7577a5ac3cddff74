"""Plain float32 reference of one block of GCN aggregation.

Imports nothing of the program: gathers each edge visit's source feature
row straight from the table, in arrival order, and adds it into its
destination's row. ``precision="bfloat16"`` rounds the rows to bfloat16
first and accumulates in bfloat16: the control.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("segments", "precision"))
def aggregate(table, src, dst, segments: int, precision: str = "float32"):
    """Rows (segments, features): row i sums the visits to vertex dst[0]+i."""
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[precision]
    rows = table[src].astype(dtype)
    out = jnp.zeros((segments, table.shape[1]), dtype)
    return out.at[dst - dst[0]].add(rows).astype(jnp.float32)
