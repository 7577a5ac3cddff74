"""Public DMA-engine op: shape-agnostic bulk copy through staging buffers.

Chunks the flat payload into ``max_transaction_bytes`` transactions (the
DMA Request Mapper), pads the tail transaction, and runs the multi-channel
kernel. A transaction is laid out as ``(rows, 128)`` — its bytes rounded
up to whole 128-lane rows — so a staging slot is a leading-axis slice of
the kernel's VMEM buffer. Value-identical to a copy of ``src``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.config import DMAConfig
from repro.kernels.dma_copy.kernel import dma_copy_chunked

_LANES = 128


def dma_copy(src: jnp.ndarray, *,
             config: DMAConfig | None = None) -> jnp.ndarray:
    config = config or DMAConfig()
    flat = src.reshape(-1)
    elem = flat.dtype.itemsize
    rows = -(-config.max_transaction_bytes // (elem * _LANES))
    chunk_elems = rows * _LANES
    n = flat.shape[0]
    num_chunks = max(1, -(-n // chunk_elems))
    pad = num_chunks * chunk_elems - n
    staged = jnp.pad(flat, (0, pad)).reshape(num_chunks, rows, _LANES)
    out = dma_copy_chunked(staged, channels=config.num_parallel_dma)
    return out.reshape(-1)[:n].reshape(src.shape)
