"""Expert-parallel MoE dispatch via shard_map all-to-all.

The paper's DMA engine, at cluster scale: each model shard owns
``E / tp`` experts; token requests are *sorted by destination shard* (the
scheduler's row = the expert's owner), packed into per-destination staging
buffers (the DMA buffers), and moved with one ``all_to_all`` bulk transfer
instead of scattered traffic. Everything inside the shard_map body is
device-local, which sidesteps the GSPMD scatter-partitioning limits the
§Perf log documents for the pure-pjit expert sharding.

Token layout: activations arrive model-replicated (Megatron convention);
the body first claims a 1/tp slice of its tokens per model shard (2D
data x model token sharding for the MoE block), dispatches with one
all_to_all each way, and all-gathers the combined outputs back to the
replicated layout — the gather replaces the dense path's output psum.

Scope: requires ``num_experts % tp == 0`` and no shared experts (jamba:
16e on the 16-way model axis → one expert per shard, Switch-style).
Capacity: per-(source, destination) send capacity — the paper's bounded
per-controller batches; dropped requests contribute zero, as in the TP
path.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import capture as capture_mod
from repro.models import layers


def moe_ffn_ep(p, x, cfg: ArchConfig, mesh, *, no_drop: bool = False):
    """EP replacement for the routed part of ``blocks.moe_ffn``.

    Returns (out, aux). Value-matches the TP dispatch at ample capacity
    (property-tested on a multi-device mesh); drop behaviour differs
    (per-destination send capacity vs per-expert capacity), inherent to EP.
    """
    m = cfg.moe
    assert m.num_shared_experts == 0, "EP path: no shared experts"
    tp = mesh.shape["model"]
    assert m.num_experts % tp == 0, "EP needs E % tp == 0"
    e_loc = m.num_experts // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    all_axes = batch_axes + ("model",)

    B, S, D = x.shape

    # Trace capture happens out here: the shard_map body only ever sees
    # tracers, so the router is re-evaluated eagerly (capture-only —
    # never feeds the data plane) to report the global dispatch.
    if capture_mod.active_capture() is not None \
            and capture_mod.is_concrete(x):
        xn_g = layers.rms_norm(x, p["ln"]).reshape(B * S, D)
        probs_g = jax.nn.softmax(
            (xn_g @ p["router"]).astype(jnp.float32), axis=-1)
        _, top_e_g = jax.lax.top_k(probs_g, m.top_k)
        from repro.models.blocks import capture_moe_dispatch
        capture_moe_dispatch(top_e_g, B * S, D, jnp.dtype(x.dtype).itemsize)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            {"ln": P(), "router": P(),
             "w_gate": P("model", None, None),
             "w_up": P("model", None, None),
             "w_down": P("model", None, None)},
            P(batch_axes, None, None),
        ),
        out_specs=(P(batch_axes, None, None), {"load_balance": P(),
                                               "router_z": P()}),
        # outputs ARE replicated over 'model' (all_gather / pmean above)
        # but the static VMA checker cannot prove it
        check_vma=False,
    )
    def body(pl, xl):
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        assert T % tp == 0, "tokens per data shard must divide the TP axis"
        t_loc = T // tp
        my = jax.lax.axis_index("model")

        xn = layers.rms_norm(xl, pl["ln"])
        # claim this model shard's token slice (2D token sharding)
        flat = jax.lax.dynamic_slice_in_dim(
            xn.reshape(T, D), my * t_loc, t_loc, axis=0)

        logits = (flat @ pl["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, m.top_k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        # aux losses over the global batch (mean over every shard's slice)
        me = jax.lax.pmean(probs.mean(0), all_axes)
        counts = jnp.zeros((m.num_experts,), jnp.float32).at[
            top_e.reshape(-1)].add(1.0) / (t_loc * m.top_k)
        ce = jax.lax.pmean(counts, all_axes)
        aux = {
            "load_balance": m.num_experts * jnp.sum(me * ce),
            "router_z": m.router_z_coef * jax.lax.pmean(
                jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2), all_axes),
        }

        # ---- scheduler: sort requests by destination shard (row owner) ---
        n = t_loc * m.top_k
        e_flat = top_e.reshape(-1)
        if no_drop:
            c_send = n
        else:
            c_send = int(math.ceil(n / tp * m.capacity_factor))
            if c_send >= 64:
                c_send = -(-c_send // 128) * 128
            c_send = min(n, c_send)
        with jax.named_scope("moe_dispatch"):
            owner = e_flat // e_loc                   # destination shard
            order = jnp.argsort(owner, stable=True)   # bitonic analogue
            owner_s = jnp.take(owner, order)
            run_start = jnp.searchsorted(owner_s, jnp.arange(tp))
            pos = (jnp.arange(n) - jnp.take(run_start, owner_s)
                   ).astype(jnp.int32)
            slot = jnp.where(pos < c_send, pos, c_send)   # drop slot

        tok_of = jnp.take(jnp.repeat(jnp.arange(t_loc), m.top_k), order)
        eid_of = jnp.take(e_flat % e_loc, order)      # local expert id

        send_tok = jnp.zeros((tp, c_send + 1, D), xl.dtype
                             ).at[owner_s, slot].set(flat[tok_of],
                                                     mode="drop")
        send_eid = jnp.full((tp, c_send + 1), e_loc, jnp.int32
                            ).at[owner_s, slot].set(eid_of, mode="drop")

        # ---- bulk transfer: one all_to_all instead of scattered traffic --
        recv_tok = jax.lax.all_to_all(send_tok[:, :c_send], "model", 0, 0,
                                      tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid[:, :c_send], "model", 0, 0,
                                      tiled=False)
        rt = recv_tok.reshape(tp * c_send, D)
        re = recv_eid.reshape(tp * c_send)

        # ---- local expert compute (everything device-local) --------------
        w_g, w_u, w_d = pl["w_gate"], pl["w_up"], pl["w_down"]
        valid = (re < e_loc)[:, None]
        if e_loc == 1:
            h = jax.nn.silu(rt @ w_g[0]) * (rt @ w_u[0])
            out_tok = jnp.where(valid, h @ w_d[0], 0.0).astype(xl.dtype)
        else:
            # small local expert count: contract through the one-hot —
            # (rows, e_loc) x (e_loc, D, F) — without materializing
            # per-token weight gathers
            onehot = jax.nn.one_hot(re, e_loc, dtype=rt.dtype)
            h = jax.nn.silu(jnp.einsum("nd,ne,edf->nf", rt, onehot, w_g)) \
                * jnp.einsum("nd,ne,edf->nf", rt, onehot, w_u)
            out_tok = jnp.einsum("nf,ne,efd->nd", h, onehot, w_d
                                 ).astype(xl.dtype)
            out_tok = jnp.where(valid, out_tok, 0)

        # ---- reverse bulk transfer + writeback in arrival order ----------
        back = jax.lax.all_to_all(out_tok.reshape(tp, c_send, D),
                                  "model", 0, 0, tiled=False)
        back = jnp.pad(back, ((0, 0), (0, 1), (0, 0)))  # re-add drop slot
        y_sorted = back[owner_s, slot]                 # (n, D)
        y = jnp.zeros((n, D), xl.dtype).at[order].set(y_sorted)
        y = y * top_p.reshape(-1)[:, None].astype(xl.dtype)
        y = y.reshape(t_loc, m.top_k, D).sum(1)        # my token slice

        # restore the model-replicated activation layout
        y_full = jax.lax.all_gather(y, "model", axis=0, tiled=True)
        return y_full.reshape(Bl, Sl, D), aux

    return body(p, x)
