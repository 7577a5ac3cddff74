"""Transformer/Mamba/MoE block forwards (train, prefill and decode paths).

The MoE dispatch is a literal instance of the paper's memory scheduler:
token→expert assignments are the request stream, the expert id is the "DRAM
row", capacity buffers are the DMA staging buffers, and the dispatch
reorders requests so all traffic to one expert is serviced as a bulk
transfer. See ``moe_ffn``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import capture as capture_mod
from repro.models import layers
from repro.models.params import mamba_dims
from repro.models.sharding import Rules, shard


class AttnCache(NamedTuple):
    k: jnp.ndarray          # (B, C, KV, hd) — C = max_len or SWA window
    v: jnp.ndarray


class QuantAttnCache(NamedTuple):
    """int8 KV cache with per-(position, head) scales (kv_cache_dtype)."""

    k: jnp.ndarray          # (B, C, KV, hd) int8
    v: jnp.ndarray          # (B, C, KV, hd) int8
    k_scale: jnp.ndarray    # (B, C, KV) f32
    v_scale: jnp.ndarray    # (B, C, KV) f32


def quantize_kv(x: jnp.ndarray):
    """Symmetric per-(.., head) int8 over the head_dim axis."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


class MambaCache(NamedTuple):
    conv_x: jnp.ndarray     # (B, 3, d_in) last conv taps
    conv_b: jnp.ndarray     # (B, 3, N)
    conv_c: jnp.ndarray     # (B, 3, N)
    ssm: jnp.ndarray        # (B, H, P, N) recurrent state


# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------

def attn_forward(p, x, cfg: ArchConfig, rules: Rules, mesh,
                 positions: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                  Optional[AttnCache]]:
    """Full-sequence attention (train / prefill). Returns (out, kv)."""
    B, S, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    xn = layers.rms_norm(x, p["ln"])
    q = (xn @ p["wq"]).reshape(B, S, h, hd)
    k = (xn @ p["wk"]).reshape(B, S, kv, hd)
    v = (xn @ p["wv"]).reshape(B, S, kv, hd)
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)
    q = shard(q, rules, "batch", "seq", "heads", None, mesh=mesh)
    k = shard(k, rules, "batch", "seq", "kv_heads", None, mesh=mesh)
    v = shard(v, rules, "batch", "seq", "kv_heads", None, mesh=mesh)
    out = layers.flash_attention(q, k, v, causal=cfg.causal,
                                 window=cfg.attn_window,
                                 q_block=cfg.attn_q_block,
                                 kv_block=cfg.attn_kv_block)
    out = out.reshape(B, S, h * hd) @ p["wo"]
    return shard(out, rules, "batch", "seq", "embed", mesh=mesh), \
        AttnCache(k=k, v=v)


def attn_prefill_cache(kv: AttnCache, cfg: ArchConfig, seq_len: int,
                       max_len: int):
    """Convert prefill K/V into the serve cache layout (ring for SWA,
    int8 quantization when configured).

    Handles an optional leading stacked-layers axis (seq axis is -3).
    """
    w = cfg.attn_window

    def pad_seq(x, target, axis=-3):
        pads = [(0, 0)] * x.ndim
        pads[axis % x.ndim] = (0, target - seq_len)
        return jnp.pad(x, pads)

    if w is None or seq_len < w:
        pad = max_len if w is None else w
        k, v = pad_seq(kv.k, pad), pad_seq(kv.v, pad)
    else:
        # ring buffer holding the last `w` tokens, slot = position % w
        sl = (Ellipsis, slice(-w, None), slice(None), slice(None))
        shift = seq_len % w
        k = jnp.roll(kv.k[sl], shift, axis=-3)
        v = jnp.roll(kv.v[sl], shift, axis=-3)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return QuantAttnCache(kq, vq, ks, vs)
    return AttnCache(k, v)


def attn_decode(p, x, cache, layer, cur_len: jnp.ndarray,
                cfg: ArchConfig, rules: Rules, mesh):
    """One-token attention of layer ``layer``; returns (out, new_cache).

    ``cache`` is the stacked cache of every layer (a leading layers axis).
    The new token's K/V row is written into it first, in place
    (``mc_kv_append``), and then the layer's own slice is read back for
    attention, so a step never rewrites the whole cache. ``cur_len`` is
    the number of
    tokens already in the cache; the new token occupies position
    ``cur_len``. Accepts either a plain ``AttnCache`` or a
    ``QuantAttnCache`` (int8 storage, dequantized at read — half the HBM
    traffic per step).
    """
    B, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    C = cache.k.shape[2]
    w = cfg.attn_window
    xn = layers.rms_norm(x, p["ln"])
    pos = jnp.full((B, 1), cur_len, jnp.int32)
    q = layers.rope((xn @ p["wq"]).reshape(B, 1, h, hd), pos, cfg.rope_theta)
    k = layers.rope((xn @ p["wk"]).reshape(B, 1, kv, hd), pos, cfg.rope_theta)
    v = (xn @ p["wv"]).reshape(B, 1, kv, hd)

    quant = isinstance(cache, QuantAttnCache)
    slot = cur_len % C if w is not None else cur_len

    def dus(buf, new):
        # KV append = the controller's bulk-write request class (fig7w).
        return layers.mc_kv_append(buf, new, slot, cfg.mc, layer=layer)

    def own(buf):
        return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)

    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        new_cache = QuantAttnCache(
            k=dus(cache.k, kq), v=dus(cache.v, vq),
            k_scale=dus(cache.k_scale, ks), v_scale=dus(cache.v_scale, vs))
        full_k = dequantize_kv(own(new_cache.k), own(new_cache.k_scale),
                               x.dtype)
        full_v = dequantize_kv(own(new_cache.v), own(new_cache.v_scale),
                               x.dtype)
    else:
        new_cache = AttnCache(*(
            shard(dus(buf, new), rules, "layers", "batch", "kv_seq", None,
                  None, mesh=mesh)
            for buf, new in ((cache.k, k), (cache.v, v))))
        full_k, full_v = own(new_cache.k), own(new_cache.v)

    n_valid = jnp.minimum(cur_len + 1, C)
    valid = jnp.broadcast_to(jnp.arange(C) < n_valid, (B, C))
    out = layers.decode_attention(q[:, 0], full_k, full_v, valid)
    out = out.reshape(B, h * hd) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# Dense / shared MLP
# ---------------------------------------------------------------------------

def mlp_forward(p, x, rules: Rules, mesh):
    xn = layers.rms_norm(x, p["ln"])
    h = jax.nn.silu(xn @ p["w_gate"]) * (xn @ p["w_up"])
    h = shard(h, rules, "batch", "seq", "heads", mesh=mesh)
    return shard(h @ p["w_down"], rules, "batch", "seq", "embed", mesh=mesh)


# ---------------------------------------------------------------------------
# MoE — the memory-controller scheduler at cluster scale
# ---------------------------------------------------------------------------

def capture_moe_dispatch(top_e, n_tokens: int, d_model: int,
                         itemsize: int) -> None:
    """Report a routed MoE layer's traffic into the active TraceCapture.

    The genuine multi-port view of expert dispatch (paper Fig. 2 /
    Nguyen et al.): **the expert id is the port** (``pe_id`` = expert —
    experts are the PEs contending for the channels), the request row is
    the *token's* activation row in the dispatch buffer region, READ on
    dispatch and WRITE on combine. ``top_e`` is ``(T, k)``; a traced
    value (jit/shard_map) skips the record, counted by the recorder.
    """
    cap = capture_mod.active_capture()
    if cap is None:
        return
    te = capture_mod.concrete(top_e)
    if te is None:
        cap.n_skipped_traced += 1
        return
    te = te.astype(np.int64)
    T, k = te.shape
    row_bytes = int(d_model) * int(itemsize)
    name = f"moe_tokens:{int(n_tokens)}x{row_bytes}"
    tok = np.repeat(np.arange(T, dtype=np.int64), k)
    pe = te.reshape(-1)
    cap.record("moe_dispatch", name, int(n_tokens), row_bytes, tok,
               rw=0, pe_id=pe)
    cap.record("moe_combine", name, int(n_tokens), row_bytes, tok,
               rw=1, pe_id=pe)


def moe_ffn(p, x, cfg: ArchConfig, rules: Rules, mesh, *,
            no_drop: bool = False, dispatch: str = "sort",
            num_groups: int = 1):
    """Token-choice top-k MoE with capacity buffers.

    Scheduler mapping (paper Fig. 2):
      requests   = (token, expert) assignments,
      row index  = expert id (the device/HBM region owning that expert),
      batch      = one *group's* assignment set (see below),
      reorder    = stable sort by row id; capacity slot = offset in the
                   expert's run (``dispatch="sort"``) — vs the naive
                   GShard one-hot prefix scan (``dispatch="cumsum"``),
      bulk xfer  = the buffer einsum against expert weights,
      writeback  = combine weighted by router prob, arrival order restored.

    ``num_groups`` partitions tokens into independent scheduler instances
    (GShard local groups), matching the paper's *bounded, per-controller*
    batches: each data shard sorts and scatters only its own requests, so
    dispatch is collective-free. Capacity is per-group; group-local drops
    are the standard GShard semantics. ``num_groups=1`` is the global
    scheduler (single-controller semantics, used on CPU/tests).

    Returns (out, aux_losses dict).
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    G = num_groups if T % max(1, num_groups) == 0 else 1
    TG = T // G
    xn = layers.rms_norm(x, p["ln"])
    flat = xn.reshape(T, D)

    logits = (flat @ p["router"]).astype(jnp.float32)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)           # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    capture_moe_dispatch(top_e, T, D, jnp.dtype(x.dtype).itemsize)

    # --- load-balance + router-z auxiliary losses (Switch/ST-MoE) ---
    me = probs.mean(0)                                     # (E,)
    ce = jnp.zeros((m.num_experts,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0) / (T * m.top_k)
    aux = {
        "load_balance": m.num_experts * jnp.sum(me * ce),
        "router_z": m.router_z_coef * jnp.mean(
            jax.nn.logsumexp(logits, axis=-1) ** 2),
    }

    # --- scheduler: place each assignment into its expert's capacity slot ---
    if no_drop:
        # Serving path: per-group capacity TG is a strict upper bound (a
        # token selects an expert at most once), so no request is ever
        # dropped and decode matches the cache-free forward exactly.
        capacity = TG
    else:
        capacity = int(math.ceil(TG * m.top_k / m.num_experts
                                 * m.capacity_factor))
        if capacity >= 64:       # round for even layout
            capacity = -(-capacity // 128) * 128
        capacity = min(capacity, TG)
    na = TG * m.top_k                            # assignments per group
    e_grp = top_e.reshape(G, na)                 # (G, n) row ids
    if dispatch == "sort":
        # Stable sort by row id per group; slot = offset in the expert's
        # contiguous run. Stability preserves arrival order within an
        # expert (same-address consistency), so slots equal the
        # sequential-arrival (cumsum) semantics without the O(n·E)
        # prefix scan.
        with jax.named_scope("moe_dispatch"):
            order = jnp.argsort(e_grp, axis=-1, stable=True)
            e_sorted = jnp.take_along_axis(e_grp, order, axis=-1)
            run_start = jax.vmap(
                lambda es: jnp.searchsorted(es, jnp.arange(m.num_experts)))(
                e_sorted)                            # (G, E)
            pos_sorted = (jnp.arange(na)[None, :]
                          - jnp.take_along_axis(run_start, e_sorted, axis=-1)
                          ).astype(jnp.int32)
            pos_in_e = jnp.zeros((G, na), jnp.int32)
            pos_in_e = jax.vmap(lambda z, o, v: z.at[o].set(v))(
                pos_in_e, order, pos_sorted)
    else:                         # "cumsum": GShard-style naive dispatch
        onehot = jax.nn.one_hot(e_grp, m.num_experts, dtype=jnp.int32)
        pos_in_e = (jnp.cumsum(onehot, axis=1) * onehot).sum(-1) - 1
    keep = pos_in_e < capacity
    slot = jnp.where(keep, pos_in_e, capacity)             # drop slot = C

    # dispatch: (G, E, C+1, D) buffers; the +1 slot swallows drops. The
    # group dim is a scatter *batch* dim sharded over data, so each shard
    # scatters only its own requests — no cross-device traffic, and no
    # GSPMD operand replication (a global capacity-sharded scatter
    # measured ~100 GiB/device of temps on qwen2 train).
    flat_g = flat.reshape(G, TG, D)
    tok_idx = jnp.repeat(jnp.arange(TG), m.top_k)
    upd = jnp.take(flat_g, tok_idx, axis=1)                # (G, n, D)
    buf = jnp.zeros((G, m.num_experts, capacity + 1, D), x.dtype)
    buf = shard(buf, rules, "expert_capacity", "expert", None, "heads",
                mesh=mesh)
    buf = jax.vmap(lambda b, e, s, u: b.at[e, s].set(u, mode="drop"))(
        buf, e_grp, slot, upd)
    buf = shard(buf[:, :, :capacity], rules, "expert_capacity", "expert",
                None, "embed", mesh=mesh)

    # bulk transfer: batched expert FFN (SwiGLU); groups stay data-sharded
    hmid = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    hmid = shard(hmid, rules, "expert_capacity", "expert", None, "heads",
                 mesh=mesh)
    eout = jnp.einsum("gecf,efd->gecd", hmid, p["w_down"])
    eout = jnp.pad(eout, ((0, 0), (0, 0), (0, 1), (0, 0)))  # drop slot
    eout = shard(eout, rules, "expert_capacity", "expert", None, "heads",
                 mesh=mesh)

    # writeback: gather each assignment's result, weight, combine per token
    y = jax.vmap(lambda eo, e, s: eo[e, s])(eout, e_grp, slot)
    y = y * top_p.reshape(G, na)[..., None].astype(x.dtype)
    y = y.reshape(G, TG, m.top_k, D).sum(2).reshape(T, D)

    if m.num_shared_experts:
        y = y + (jax.nn.silu(flat @ p["shared_gate"])
                 * (flat @ p["shared_up"])) @ p["shared_down"]

    out = y.reshape(B, S, D)
    return shard(out, rules, "batch", "seq", "embed", mesh=mesh), aux


# ---------------------------------------------------------------------------
# Mamba2 (SSD) block
# ---------------------------------------------------------------------------

def _causal_conv(u, w, cache=None):
    """Depthwise causal conv, kernel 4. u: (B, S, C), w: (4, C).

    With ``cache`` (B, 3, C) the first taps come from previous context
    (decode path handles S=1)."""
    if cache is None:
        pad = jnp.zeros((u.shape[0], 3, u.shape[2]), u.dtype)
    else:
        pad = cache.astype(u.dtype)
    full = jnp.concatenate([pad, u], axis=1)               # (B, S+3, C)
    out = sum(full[:, i:i + u.shape[1]] * w[i] for i in range(4))
    new_cache = full[:, -3:]
    return jax.nn.silu(out), new_cache


def _mamba_bc_dt(p, xn, n: int):
    bc = xn @ p["w_bc"]
    b, c = bc[..., :n], bc[..., n:]
    dt = jax.nn.softplus((xn @ p["w_dt"]).astype(jnp.float32)
                         + p["dt_bias"])                   # (B, S, H)
    return b, c, dt


def _mamba_project(p, x, cfg: ArchConfig):
    d_in, nh, hp, n = mamba_dims(cfg)
    xn = layers.rms_norm(x, p["ln"])
    zx = xn @ p["w_zx"]
    z, xin = zx[..., :d_in], zx[..., d_in:]
    return (z, xin) + _mamba_bc_dt(p, xn, n)


def mamba_forward(p, x, cfg: ArchConfig, rules: Rules, mesh
                  ) -> Tuple[jnp.ndarray, MambaCache]:
    """Chunked SSD forward (Mamba-2, arXiv:2405.21060 §6, "minimal SSD").

    The chunks are a reshape of the sequence, and the SSD runs on ``x`` and
    ``z`` head-major, (B, nc, H, P, L), in the projection's dtype: ``z``
    comes out of its projection transposed (the MXU writes the transposed
    product at the cost of the plain one), ``x`` is moved once after the
    causal conv, and ``wo`` contracts over (H, P) in that layout, so no
    float32 copy of ``x`` or ``y`` changes layout. All chunks at once: the
    intra-chunk (diagonal) term as one dense, quadratic-in-chunk
    contraction batched over (batch, chunk, head), and each chunk's end
    state as one contraction over its positions. Only the inter-chunk
    recurrence is sequential: a loop over the chunks that carries the
    (B, H, P, N) float32 state. Returns the final state as decode cache.
    """
    B, S, D = x.shape
    d_in, H, P, N = mamba_dims(cfg)
    L = min(cfg.ssm.chunk, S)
    Sp = -(-S // L) * L
    nc = Sp // L
    f32 = jnp.float32

    xn = layers.rms_norm(x, p["ln"])
    xin = xn @ p["w_zx"][:, d_in:]
    b, c, dt = _mamba_bc_dt(p, xn, N)
    with jax.named_scope("mamba_conv"):
        xin, conv_x = _causal_conv(xin, p["conv_x"])
        b, conv_b = _causal_conv(b, p["conv_b"])
        c, conv_c = _causal_conv(c, p["conv_c"])
    a = -jnp.exp(p["a_log"])                               # (H,) negative

    # Pad to a chunk multiple. Padded positions get dt=0, which makes them
    # exactly transparent: zero state contribution, unchanged decay.
    if Sp != S:
        pad3 = lambda t: jnp.pad(t, ((0, 0), (0, Sp - S), (0, 0)))
        xn, xin, b, c, dt = pad3(xn), pad3(xin), pad3(b), pad3(c), pad3(dt)
        valid = (jnp.arange(Sp) < S).astype(dt.dtype)
        dt = dt * valid[None, :, None]

    # Head-major chunks, (B, nc, H, P, L), in the projection's dtype: z
    # comes out of its projection transposed, and x, which the causal conv
    # needs token-major, is moved once.
    zh = jnp.einsum("bcld,dk->bckl", xn.reshape(B, nc, L, D),
                    p["w_zx"][:, :d_in]).reshape(B, nc, H, P, L)
    xh = xin.reshape(B, nc, L, d_in).transpose(0, 1, 3, 2).reshape(
        B, nc, H, P, L)
    bc_ = b.reshape(B, nc, L, N).astype(f32)
    cc_ = c.reshape(B, nc, L, N).astype(f32)
    dth = dt.reshape(B, nc, L, H).transpose(0, 1, 3, 2)   # (B, nc, H, L)
    xf = xh.astype(f32)

    with jax.named_scope("ssd_chunk_scan"):
        cum = jnp.cumsum(dth * a[:, None], axis=-1)       # (B, nc, H, L)
        # intra-chunk: M[h,l,m] = exp(cum_l - cum_m) * (c_l·b_m) * dt_m for
        # l >= m. Masked before exp: cum_l - cum_m > 0 above the diagonal
        # can overflow, and an inf there makes the gradient NaN.
        scores = jnp.einsum("bcln,bcmn->bclm", cc_, bc_)
        causal = jnp.tril(jnp.ones((L, L), bool))
        seg = jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                        -jnp.inf)
        mmat = (jnp.exp(seg) * scores[:, :, None]
                * dth[:, :, :, None, :])                   # (B, nc, H, L, M)
        y = jnp.einsum("bchpm,bchlm->bchpl", xf, mmat)
        # each chunk's end state: sum_l exp(cum_L - cum_l) dt_l x_l b_l
        tail = jnp.exp(cum[..., -1:] - cum) * dth          # (B, nc, H, L)
        states = jnp.einsum("bchpl,bcln->bchpn",
                            xf * tail[:, :, :, None], bc_)
        # inter-chunk recurrence: the state entering each chunk
        g = jnp.exp(cum[..., -1])                          # (B, nc, H)

        def enter(h, ci):
            g_c = jax.lax.dynamic_index_in_dim(g, ci, 1, keepdims=False)
            s_c = jax.lax.dynamic_index_in_dim(states, ci, 1, keepdims=False)
            return h * g_c[:, :, None, None] + s_c, h

        h0 = jnp.zeros((B, H, P, N), f32)
        h_final, h_in = jax.lax.scan(enter, h0, jnp.arange(nc))
        # state -> output: exp(cum_l) c_l · h_in (h_in is chunk-major)
        y += jnp.exp(cum)[:, :, :, None] * jnp.einsum(
            "cbhpn,bcln->bchpl", h_in, cc_)

    y = y + xf * p["d_skip"][None, None, :, None, None]
    y = y * jax.nn.silu(zh.astype(f32))
    y = layers.rms_norm(y.astype(x.dtype).reshape(B, nc, d_in, L),
                        p["gated_ln"][:, None], axis=2)
    # wo contracts over (H, P) with y head-major. The barrier keeps the
    # caller's residual add out of the dot's fusion: fused with it, the TPU
    # compiler copies y token-major first (one v5e, 8 x 2048: 14.2 ms a
    # layer with the barrier, 15.1 without).
    out = jax.lax.optimization_barrier(
        jnp.einsum("bckl,kd->bcld", y, p["wo"]))
    out = out.reshape(B, Sp, D)[:, :S]
    cache = MambaCache(conv_x=conv_x, conv_b=conv_b, conv_c=conv_c,
                       ssm=h_final)
    return shard(out, rules, "batch", "seq", "embed", mesh=mesh), cache


def mamba_decode(p, x, stacked: MambaCache, layer, cfg: ArchConfig,
                 rules: Rules, mesh) -> Tuple[jnp.ndarray, MambaCache]:
    """O(1) recurrent step of layer ``layer``. x: (B, D).

    ``stacked`` holds every layer's state (a leading layers axis); the
    layer's whole state is read out and its new state written back into
    the stacked buffers."""
    B, D = x.shape
    with jax.named_scope("ssm_state_step"):
        cache = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, layer, 0,
                                                   keepdims=False),
            stacked)
    d_in, H, P, N = mamba_dims(cfg)
    cap = capture_mod.active_capture()
    if cap is not None and capture_mod.is_concrete(x):
        # SSM family signature: every decode step rewrites the whole
        # (H, P, N) recurrent state — a wide sequential page-write burst
        # per sequence (port = sequence), nothing like KV's single-slot
        # append. Static shapes, so gate on x being concrete to avoid
        # recording during jit tracing.
        page_bytes = P * N * 4                      # f32 state rows
        cap.record("ssm_state_update", f"ssm:{H}x{page_bytes}", H,
                   page_bytes, np.tile(np.arange(H, dtype=np.int64), B),
                   rw=1, pe_id=np.repeat(np.arange(B, dtype=np.int64), H))
    z, xin, b, c, dt = _mamba_project(p, x[:, None, :], cfg)
    with jax.named_scope("mamba_conv"):
        xin, conv_x = _causal_conv(xin, p["conv_x"], cache.conv_x)
        b, conv_b = _causal_conv(b, p["conv_b"], cache.conv_b)
        c, conv_c = _causal_conv(c, p["conv_c"], cache.conv_c)

    xh = xin[:, 0].reshape(B, H, P).astype(jnp.float32)
    dt1 = dt[:, 0]                                         # (B, H)
    b1 = b[:, 0].astype(jnp.float32)                       # (B, N)
    c1 = c[:, 0].astype(jnp.float32)
    a = -jnp.exp(p["a_log"])

    with jax.named_scope("ssm_state_step"):
        da = jnp.exp(dt1 * a)                              # (B, H)
        h_new = (cache.ssm * da[:, :, None, None]
                 + jnp.einsum("bh,bhp,bn->bhpn", dt1, xh, b1))
        y = jnp.einsum("bhpn,bn->bhp", h_new, c1)
    y = y + xh * p["d_skip"][None, :, None]
    y = (y.reshape(B, d_in)
         * jax.nn.silu(z[:, 0].astype(jnp.float32)))
    y = layers.rms_norm(y.astype(x.dtype), p["gated_ln"])
    out = y @ p["wo"]
    with jax.named_scope("ssm_state_step"):
        stacked = jax.tree.map(
            lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                buf, new, layer, 0),
            stacked, MambaCache(conv_x, conv_b, conv_c, h_new))
    return out, stacked
