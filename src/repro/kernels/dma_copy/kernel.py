"""DMA engine data plane — multi-channel double-buffered bulk copy.

Implements the paper's DMA engine (§IV-B) with TPU async copies: the
``num_parallel_dma`` FPGA buffers become ``channels`` VMEM staging slots,
each with inbound/outbound DMA semaphores. The kernel keeps up to
``channels`` inbound HBM→VMEM copies in flight while draining completed
slots back out — bulk transfers overlap exactly like parallel FPGA DMAs,
and ``max_transaction_bytes`` maps to the chunk (block) size.

Structure per chunk ``c`` on channel ``ch = c % channels``:
  wait outbound[ch] (slot free) → start inbound c → ... (channels in
  flight) ... → wait inbound[ch] → start outbound c.

Each chunk is a ``(rows, 128)`` tile stack, so a channel slot is a whole
leading-axis slice of the ``(channels, rows, 128)`` staging buffer: the
TPU only slices VMEM along untiled axes or at (8, 128) tile boundaries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _dma_copy_kernel(in_ref, out_ref, scratch, in_sems, out_sems,
                     *, channels: int):
    num_chunks = in_ref.shape[0]

    def inbound(c):
        ch = c % channels
        return pltpu.make_async_copy(in_ref.at[c], scratch.at[ch],
                                     in_sems.at[ch])

    def outbound(c):
        ch = c % channels
        return pltpu.make_async_copy(scratch.at[ch], out_ref.at[c],
                                     out_sems.at[ch])

    # Prologue: fill every channel with an in-flight inbound transfer.
    for ch in range(min(channels, num_chunks)):
        inbound(ch).start()

    def body(c, _):
        # Land chunk c, ship it out, and immediately refill the channel
        # with chunk c+channels (if any).
        inbound(c).wait()
        outbound(c).start()
        nxt = c + channels

        @pl.when(nxt < num_chunks)
        def _():
            # Slot reuse hazard: the outbound of chunk c must complete
            # before its scratch slot is overwritten by chunk c+channels.
            outbound(c).wait()
            inbound(nxt).start()

        return 0

    jax.lax.fori_loop(0, num_chunks, body, 0)

    # Epilogue: drain the tail outbound transfers that were never waited
    # on by a refill.
    tail = max(0, num_chunks - channels)
    for c in range(tail, num_chunks):
        outbound(c).wait()


@functools.partial(jax.jit, static_argnames=("channels", "interpret"))
def dma_copy_chunked(src: jnp.ndarray, *, channels: int = 4,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Copy ``src (num_chunks, rows, 128)`` through the staging pipeline.
    ``interpret=None`` interprets on the CPU backend only."""
    num_chunks, rows, lanes = src.shape
    return pl.pallas_call(
        functools.partial(_dma_copy_kernel, channels=channels),
        in_specs=[pl.BlockSpec(memory_space=pl.MemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.MemorySpace.ANY),
        out_shape=jax.ShapeDtypeStruct(src.shape, src.dtype),
        scratch_shapes=[
            pltpu.VMEM((channels, rows, lanes), src.dtype),
            pltpu.SemaphoreType.DMA((channels,)),
            pltpu.SemaphoreType.DMA((channels,)),
        ],
        interpret=interpret_default() if interpret is None else interpret,
    )(src)
