"""Shared NN layers: norms, RoPE, memory-efficient attention, embeddings.

Attention comes in two forms:

* ``flash_attention`` — train/prefill path. Double-blocked online-softmax
  attention (q-blocks outer scan, kv-blocks inner scan) so the score matrix
  never materializes; this is the XLA expression of the paper's DMA engine
  streaming KV through VMEM-sized staging buffers. Causal, bidirectional
  and sliding-window masks supported. The Pallas twin lives in
  ``repro.kernels.flash_attention``.
* ``decode_attention`` — one-token serve path against a (possibly
  ring-buffered) KV cache; works with the cache sequence dim sharded across
  the mesh (flash-decoding style distributed softmax — XLA inserts the
  small all-reduces for max/sum).

Embedding traffic routes through the memory controller in both
directions: lookups via ``mc_embed`` (token ids stable-sorted per sequence
before the table gather) and table updates via ``mc_scatter`` (the
embedding-gradient WRITE stream, batch-sorted and coalesced per row);
``mc_kv_append`` is the decode-step KV page write on the DMA bulk path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import capture as capture_mod
from repro.core.config import MemoryControllerConfig


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6,
             axis=-1):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=axis, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """Rotary embedding. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


# ---------------------------------------------------------------------------
# Memory-efficient attention (XLA path)
# ---------------------------------------------------------------------------

def _mask_value(dtype):
    return jnp.asarray(-0.7 * jnp.finfo(jnp.float32).max, jnp.float32)


def flash_attention(
    q: jnp.ndarray,               # (B, S, H, hd)
    k: jnp.ndarray,               # (B, S, KV, hd)
    v: jnp.ndarray,               # (B, S, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_block: int = 512,
    kv_block: int = 1024,
) -> jnp.ndarray:
    """Online-softmax attention; O(S·block) memory instead of O(S²)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV                   # GQA group size
    scale = hd ** -0.5

    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    # pad S to multiples
    Sq = -(-S // q_block) * q_block
    Sk = -(-S // kv_block) * kv_block
    qp = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0), (0, 0)))

    # (B, KV, G, S, hd) grouped layout
    qg = qp.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)
    kg = kp.transpose(0, 2, 1, 3)  # (B, KV, Sk, hd)
    vg = vp.transpose(0, 2, 1, 3)

    nq, nk = Sq // q_block, Sk // kv_block
    neg = _mask_value(q.dtype)

    def q_step(_, qi):
        qi0 = qi * q_block
        q_blk = jax.lax.dynamic_slice_in_dim(qg, qi0, q_block, axis=3)
        q_pos = qi0 + jnp.arange(q_block)

        def kv_step(carry, ki):
            o, m, l = carry
            ki0 = ki * kv_block
            k_blk = jax.lax.dynamic_slice_in_dim(kg, ki0, kv_block, axis=2)
            v_blk = jax.lax.dynamic_slice_in_dim(vg, ki0, kv_block, axis=2)
            k_pos = ki0 + jnp.arange(kv_block)

            s = jnp.einsum("bkgqd,bkcd->bkgqc", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            mask = k_pos[None, :] <= (q_pos[:, None] if causal
                                      else jnp.full_like(q_pos[:, None],
                                                         Sk - 1))
            if window is not None:
                mask &= k_pos[None, :] > (q_pos[:, None] - window)
            mask &= (k_pos < S)[None, :]
            s = jnp.where(mask[None, None, None], s, neg)

            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            pv = jnp.einsum("bkgqc,bkcd->bkgqd", p, v_blk,
                            preferred_element_type=jnp.float32)
            o_new = o * corr[..., None] + pv
            return (o_new, m_new, l_new), None

        o0 = jnp.zeros((B, KV, G, q_block, hd), jnp.float32)
        m0 = jnp.full((B, KV, G, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        (o, m, l), _ = jax.lax.scan(kv_step, (o0, m0, l0), jnp.arange(nk))
        out_blk = o / jnp.maximum(l[..., None], 1e-37)
        return None, out_blk.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq))
    # blocks: (nq, B, KV, G, q_block, hd) → (B, S, H, hd)
    out = blocks.transpose(1, 2, 3, 0, 4, 5).reshape(B, KV, G, Sq, hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)
    return out[:, :S]


def decode_attention(
    q: jnp.ndarray,               # (B, H, hd) — one new token per sequence
    cache_k: jnp.ndarray,         # (B, Sc, KV, hd)
    cache_v: jnp.ndarray,
    valid_mask: jnp.ndarray,      # (B, Sc) bool — which cache slots attend
) -> jnp.ndarray:
    B, H, hd = q.shape
    KV = cache_k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid_mask[:, None, None, :], s, _mask_value(q.dtype))
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, cache_v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Controller-routed embedding
# ---------------------------------------------------------------------------

def _embed_region(table: jnp.ndarray) -> tuple:
    """(region_name, n_rows, row_bytes) of an embedding table — shared by
    ``mc_embed`` (READ) and ``mc_scatter`` (WRITE) so both directions of
    embedding traffic land on the same captured rows."""
    n_rows = int(table.shape[0])
    row_bytes = int(table.shape[-1]) * int(np.dtype(table.dtype).itemsize)
    return f"embed:{n_rows}x{row_bytes}", n_rows, row_bytes


def _capture_embed(op: str, table, tokens, rw: int) -> None:
    cap = capture_mod.active_capture()
    if cap is None:
        return
    name, n_rows, row_bytes = _embed_region(table)
    shape = tuple(tokens.shape)
    if len(shape) >= 2:
        # one port per sequence (leading dims flattened): the multi-PE
        # front end sees each sequence's token stream on its own port
        lead = int(np.prod(shape[:-1]))
        pe = np.repeat(np.arange(lead, dtype=np.int64), shape[-1])
    else:
        pe = 0          # single-sequence / decode stream — one port
    cap.record(op, name, n_rows, row_bytes, tokens, rw=rw, pe_id=pe)


@jax.named_scope("mc_embed")
def mc_embed(table: jnp.ndarray, tokens: jnp.ndarray,
             mc: MemoryControllerConfig) -> jnp.ndarray:
    """Embedding gather through the memory controller's scheduler.

    Requests are stable-sorted *per sequence* (axis -1) — each sequence is
    one scheduler batch, matching the paper's bounded batch size. 1-D (and
    scalar) token streams — the decode-step path — are one sequence, so
    the whole stream forms a single scheduler batch instead of bypassing
    the controller. Value-identical to ``table[tokens]``.
    """
    _capture_embed("embed_gather", table, tokens, rw=0)
    if not mc.scheduler.enabled:
        return jnp.take(table, tokens, axis=0)
    if tokens.ndim < 2:
        flat = tokens.reshape(-1)
        perm = jnp.argsort(flat, stable=True)
        gathered = jnp.take(table, jnp.take(flat, perm, axis=0), axis=0)
        inv = jnp.argsort(perm, stable=True)
        out = jnp.take(gathered, inv, axis=0)
        return out.reshape(*tokens.shape, table.shape[-1])
    perm = jnp.argsort(tokens, axis=-1, stable=True)
    sorted_tok = jnp.take_along_axis(tokens, perm, axis=-1)
    gathered = jnp.take(table, sorted_tok, axis=0)
    inv = jnp.argsort(perm, axis=-1, stable=True)
    return jnp.take_along_axis(gathered, inv[..., None], axis=-2)


@jax.named_scope("mc_scatter")
def mc_scatter(table: jnp.ndarray, tokens: jnp.ndarray,
               values: jnp.ndarray, mc: MemoryControllerConfig,
               *, mode: str = "add") -> jnp.ndarray:
    """Embedding write through the memory controller's scheduler.

    The write-side twin of :func:`mc_embed`: the backward of an embedding
    lookup is an irregular scatter of per-token rows into the table
    (gradient accumulation, ``mode="add"``), the same WRITE stream the
    controller batch-sorts by row. Value-identical to
    ``table.at[tokens].add(values)`` / last-writer-wins ``set``.
    """
    from repro.core.controller import MemoryController
    _capture_embed("embed_scatter", table, tokens, rw=1)
    return MemoryController(mc).scatter(table, tokens, values, mode=mode)


#: Slots an append rewrites around the new one: a TPU tile's lane width.
#: Where the head dim is not a multiple of 128 (80 in h2o-danube-1.8b), a
#: TPU keeps the cache with the slot axis minor, so one slot is one lane
#: of its tiles. A one-slot update then makes the compiler lay the whole
#: cache out anew around the decode loop (a copy of it in and out of
#: every step); a block as wide as a tile keeps the layout, in place.
KV_WRITE_SLOTS = 128


@jax.named_scope("mc_kv_append")
def mc_kv_append(buf: jnp.ndarray, new: jnp.ndarray, slot,
                 mc: MemoryControllerConfig, axis: int = 1,
                 layer=None) -> jnp.ndarray:
    """One decode-step KV append — the controller's bulk-write request
    class.

    ``new`` is one row (size 1 on ``axis``), stored at ``slot``, clamped
    into the buffer like ``lax.dynamic_update_slice``. With ``layer``,
    ``buf`` is the stacked cache of every layer (a leading layers axis)
    and the row lands in ``buf[layer]``; ``axis`` counts within that
    layer's slice. The row is written as the ``KV_WRITE_SLOTS``-slot block
    that holds it, every other slot of the block unchanged, so a step
    moves one block per layer, in place where ``buf`` is a loop carry or
    a donated argument.

    A cache row is a contiguous page, so the append is classified as a
    bulk/streaming write (cache-bypassing), not an irregular scatter;
    its DRAM cost is the ``fig7_write_kv_append`` paper-figure test case.
    The data plane is the same for every engine setting; ``mc`` marks
    the request class, which the capture hook reports as ``kv_append``
    bulk-write records (op label suffixed ``_dma`` when the config's DMA
    engine owns the stream) — never affecting stored values.
    """
    if new.shape[axis] != 1:
        raise ValueError(f"one row per append, got {new.shape[axis]}")
    starts = [0] * buf.ndim
    sizes = list(buf.shape)
    if layer is not None:
        new = new[None]
        axis += 1
        starts[0], sizes[0] = layer, 1
    pages = int(buf.shape[axis])
    cap = capture_mod.active_capture()
    if cap is not None:
        page_bytes = (int(np.prod(new.shape))
                      * int(np.dtype(new.dtype).itemsize))
        op = "kv_append_dma" if mc.dma.enabled else "kv_append"
        cap.record_slice(op, f"kv:{pages}x{page_bytes}", pages, page_bytes,
                         slot, 1, rw=1)
    slot = jnp.clip(slot, 0, pages - 1)
    width = min(pages, KV_WRITE_SLOTS)
    base = slot // width * width
    if pages % width:       # the last block ends at the buffer's end
        base = jnp.minimum(base, pages - width)
    starts[axis], sizes[axis] = base, width
    # No start is negative. Without the wrap-around of negative starts the
    # TPU compiler can tell the block is tile-aligned, and fuses the read,
    # the select and the write into one in-place update.
    block = jax.lax.dynamic_slice(buf, starts, sizes,
                                  allow_negative_indices=False)
    hit = (jnp.arange(width) == slot - base).reshape(
        [width if d == axis else 1 for d in range(buf.ndim)])
    return jax.lax.dynamic_update_slice(buf, jnp.where(hit, new, block),
                                        starts, allow_negative_indices=False)
