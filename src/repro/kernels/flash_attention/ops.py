"""Public flash-attention op in the model layout (B, S, H, hd)."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas


def flash_attention(q, k, v, *, causal=True, window=None,
                    q_block=128, kv_block=128):
    """GQA flash attention; value-matches ``ref.attention_ref``."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    q_block = min(q_block, S)
    kv_block = min(kv_block, S)
    out = flash_attention_pallas(qf, kf, vf, group=G, causal=causal,
                                 window=window, q_block=q_block,
                                 kv_block=kv_block)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
