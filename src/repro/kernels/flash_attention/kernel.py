"""Flash attention — the DMA engine applied to KV-cache streaming.

The paper's DMA engine stages bulk transfers through parallel on-chip
buffers so the PE never waits on DRAM; here K/V blocks stream HBM→VMEM
through the Pallas pipeline (auto double-buffered) while the online-softmax
accumulators live entirely in VMEM scratch — the accumulator traffic that
dominates the XLA-path memory term (§Perf refuted-hypothesis log) simply
does not exist on this path.

Block-causal skip: fully-masked KV blocks are skipped with ``pl.when``
(compute) and their fetches deduped by clamping the block index map to the
last useful block (the Pallas pipeline skips refetching an unchanged
block) — the ragged-causal FLOP saving the dense XLA path cannot express.

Layout: one (batch, head) pair per grid row; GQA folds kv_head = head // G
into the K/V index maps, so grouped queries share the same streamed block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *,
                  q_block: int, kv_block: int, nk: int, causal: bool,
                  window, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    q_start = qi * q_block
    k_start = ki * kv_block
    # block is live unless causality/window excludes it entirely
    live = jnp.bool_(True)
    if causal:
        live = k_start <= q_start + q_block - 1
    if window is not None:
        live = jnp.logical_and(live,
                               k_start + kv_block > q_start - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 1)
        mask = jnp.ones((q_block, kv_block), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_i[:, 0]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_i[:, 0] = l_i[:, 0] * corr + p.sum(-1)
        acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_i[:, 0] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc[...] /
                    jnp.maximum(l_i[:, 0], 1e-37)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "group", "causal", "window", "q_block", "kv_block", "interpret"))
def flash_attention_pallas(
    q: jnp.ndarray,           # (BH, S, hd) — flattened (batch, head)
    k: jnp.ndarray,           # (BKV, S, hd)
    v: jnp.ndarray,
    *,
    group: int,               # q heads per kv head (GQA)
    causal: bool = True,
    window=None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool | None = None,   # None: interpret on the CPU backend
):
    BH, S, hd = q.shape
    scale = hd ** -0.5
    nq = S // q_block
    nk = S // kv_block
    assert S % q_block == 0 and S % kv_block == 0

    def kv_index(bh, qi, ki):
        # clamp skipped (fully-masked) blocks to the last live one: the
        # pipeline dedups the repeated fetch (row-buffer-hit economics)
        if causal:
            last_live = ((qi + 1) * q_block - 1) // kv_block
            ki = jnp.minimum(ki, last_live)
        return (bh // group, ki, 0)

    grid = (BH, nq, nk)
    kernel = functools.partial(
        _flash_kernel, q_block=q_block, kv_block=kv_block, nk=nk,
        causal=causal, window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kv_block, hd), kv_index),
            pl.BlockSpec((1, kv_block, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, hd), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
        ],
        interpret=interpret_default() if interpret is None else interpret,
    )(q, k, v)
