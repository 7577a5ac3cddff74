"""Pallas TPU kernels for the memory-controller hot paths.

Each kernel directory carries ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted public wrapper) and ``ref.py`` (pure-jnp oracle used by
the allclose test sweeps):

* ``bitonic_sort``  — the scheduler's reordering network (paper Fig. 2)
* ``sorted_gather`` — locality gather; Pallas revisit-skip = row-buffer hit
* ``cache_lookup``  — set-associative tag/LRU pipelines (paper Fig. 3/4)
* ``dma_copy``      — multi-channel double-buffered bulk engine (paper §IV-B)
* ``flash_attention`` — chunked attention; the DMA engine applied to KV streaming

Kernels target the TPU (VMEM tiling, async copies) and compile there.
On the CPU backend they run in the Pallas interpreter, which is how the
test suite checks them against ``ref.py``; :func:`interpret_default`
makes that choice from the backend, never from a caller's flag. The
controller reaches them through ``MemoryController(use_pallas=True)``.
"""

import jax


def interpret_default() -> bool:
    """Interpret kernels only where no TPU compiler stands behind them."""
    return jax.default_backend() == "cpu"
