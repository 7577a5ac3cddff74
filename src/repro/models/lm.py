"""Unified LM assembly for all assigned architectures.

One ``LM`` class executes every family (dense/SSM/MoE/hybrid/encoder/VLM)
by walking the config's layer pattern. Layers are stacked and executed with
``lax.scan`` over pattern periods — one period of HLO regardless of depth,
which keeps 88-layer dry-run compiles fast — with ``jax.checkpoint`` remat
inside the scan for training.

Entry points (the shape cells map onto these):
  ``loss``        → train_4k        (fwd+CE; train_step wraps with grad/opt)
  ``prefill``     → prefill_32k     (full forward, returns serve cache)
  ``decode_step`` → decode_32k / long_500k (one token, cache update)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks, layers
from repro.models.blocks import AttnCache, MambaCache
from repro.models.params import (abstract_params, init_params, mamba_dims,
                                 param_specs)
from repro.models.sharding import (Rules, make_rules, named_shardings,
                                   shard)


@dataclasses.dataclass
class LM:
    cfg: ArchConfig
    rules: Rules
    mesh: Any = None
    moe_strategy: str = "tp"

    # ---------------- params ------------------------------------------------
    def init(self, key):
        """Random parameters. On a mesh each leaf is created in its own
        sharding, so no device ever holds the whole model."""
        if self.mesh is None:
            return init_params(self.cfg, key)
        return jax.jit(partial(init_params, self.cfg),
                       out_shardings=named_shardings(
                           self.mesh, self.param_specs()))(key)

    def abstract_params(self):
        return abstract_params(self.cfg)

    def param_specs(self):
        return param_specs(self.cfg, self.rules)

    # ---------------- input embedding --------------------------------------
    @staticmethod
    def _capture_frontend(op: str, frames) -> None:
        """Report an audio/vision frontend's (B, S, F) embedding stream as
        sequential bulk reads — one page per frame/patch, one port per
        sequence. Purely observational (the data plane is the matmul
        below); skipped under tracing like every capture hook."""
        from repro.core import capture as capture_mod
        cap = capture_mod.active_capture()
        if cap is None:
            return
        if not capture_mod.is_concrete(frames):
            cap.n_skipped_traced += 1
            return
        import numpy as np
        B, S, F = frames.shape
        page_bytes = int(F) * int(jnp.dtype(frames.dtype).itemsize)
        cap.record(op, f"{op}:{B * S}x{page_bytes}", B * S, page_bytes,
                   np.arange(B * S, dtype=np.int64), rw=0,
                   pe_id=np.repeat(np.arange(B, dtype=np.int64), S))

    def _embed_inputs(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (x (B,S,D), loss_mask (B,S))."""
        cfg = self.cfg
        if cfg.modality == "audio":
            frames = batch["frames"]
            self._capture_frontend("audio_frames", frames)
            x = frames @ params["connector"]["w"]
            x = layers.rms_norm(x, params["connector"]["ln"])
            mask = jnp.ones(x.shape[:2], jnp.float32)
        elif cfg.modality == "vision_text":
            self._capture_frontend("vision_patches", batch["vision_embeds"])
            vis = batch["vision_embeds"] @ params["connector"]["w"]
            vis = layers.rms_norm(vis, params["connector"]["ln"])
            txt = layers.mc_embed(params["embed"]["table"], batch["tokens"],
                                  cfg.mc)
            x = jnp.concatenate([vis.astype(txt.dtype), txt], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros(vis.shape[:2], jnp.float32),
                 jnp.ones(txt.shape[:2], jnp.float32)], axis=1)
        else:
            x = layers.mc_embed(params["embed"]["table"], batch["tokens"],
                                cfg.mc)
            mask = jnp.ones(x.shape[:2], jnp.float32)
        if "loss_mask" in batch:
            pad = mask.shape[1] - batch["loss_mask"].shape[1]
            lm_mask = jnp.pad(batch["loss_mask"].astype(jnp.float32),
                              ((0, 0), (pad, 0)))
            mask = mask * lm_mask
        x = shard(x, self.rules, "batch", "seq", "embed", mesh=self.mesh)
        return x, mask

    def embedding_grad_update(self, params, tokens: jnp.ndarray,
                              grad_rows: jnp.ndarray, lr: float = 1.0):
        """Apply a sparse embedding update through the controller write path.

        ``grad_rows`` holds one gradient row per token occurrence (the
        backward of ``mc_embed``); rows for repeated tokens accumulate —
        the controller's scheduler stable-sorts the WRITE batch by row and
        coalesces duplicates before touching HBM (``mc_scatter``,
        mode="add"). Value-identical to
        ``table.at[tokens].add(-lr * grad_rows)``. Returns params with the
        updated table; every other leaf is shared, not copied.
        """
        table = params["embed"]["table"]
        new_table = layers.mc_scatter(
            table, tokens, (-lr * grad_rows).astype(table.dtype),
            self.cfg.mc, mode="add")
        return {**params, "embed": {**params["embed"], "table": new_table}}

    def _full_labels(self, batch, S: int) -> jnp.ndarray:
        labels = batch["labels"]
        pad = S - labels.shape[1]
        if pad:
            labels = jnp.pad(labels, ((0, 0), (pad, 0)))   # vision prefix
        return labels

    def _moe_groups(self, x) -> int:
        """Scheduler instances for MoE dispatch = data-parallel shards of
        the token batch (per-controller bounded batches, paper §II). Falls
        back to 1 (global scheduler) off-mesh or when batch doesn't
        divide."""
        if self.mesh is None:
            return 1
        axes = self.rules.batch
        if axes is None:
            return 1
        axes = (axes,) if isinstance(axes, str) else axes
        g = 1
        for a in axes:
            g *= self.mesh.shape[a]
        B = x.shape[0]
        return g if g > 0 and B % g == 0 else 1

    # ---------------- block walker ------------------------------------------
    def _run_block(self, bp, x, layer_pos: int, positions,
                   mode: str, cache=None, cur_len=None, layer=None):
        """One (mixer, ffn) sub-block with residuals.

        In decode, ``cache`` is this position's stacked cache and
        ``layer`` the group it belongs to. Returns (x, aux_losses,
        new_cache)."""
        cfg, rules, mesh = self.cfg, self.rules, self.mesh
        aux = {"load_balance": jnp.zeros((), jnp.float32),
               "router_z": jnp.zeros((), jnp.float32)}
        new_cache = {}
        if "attn" in bp:
            if mode == "decode":
                out, kv = blocks.attn_decode(bp["attn"], x, cache["attn"],
                                             layer, cur_len, cfg, rules,
                                             mesh)
            else:
                out, kv = blocks.attn_forward(bp["attn"], x, cfg, rules,
                                              mesh, positions)
            x = x + out
            new_cache["attn"] = kv
        elif "mamba" in bp:
            if mode == "decode":
                out, mc = blocks.mamba_decode(bp["mamba"], x, cache["mamba"],
                                              layer, cfg, rules, mesh)
            else:
                out, mc = blocks.mamba_forward(bp["mamba"], x, cfg, rules,
                                               mesh)
            x = x + out
            new_cache["mamba"] = mc
        if "mlp" in bp:
            if mode == "decode":
                x = x + blocks.mlp_forward(bp["mlp"], x[:, None, :],
                                           self.rules, mesh)[:, 0]
            else:
                x = x + blocks.mlp_forward(bp["mlp"], x, self.rules, mesh)
        elif "moe" in bp:
            xin = x[:, None, :] if mode == "decode" else x
            if self.moe_strategy == "ep":
                from repro.models.moe_ep import moe_ffn_ep
                out, moe_aux = moe_ffn_ep(bp["moe"], xin, cfg, mesh,
                                          no_drop=(mode == "decode"))
            else:
                out, moe_aux = blocks.moe_ffn(
                    bp["moe"], xin, cfg, self.rules, mesh,
                    no_drop=(mode == "decode"), dispatch=cfg.moe_dispatch,
                    num_groups=self._moe_groups(xin))
            x = x + (out[:, 0] if mode == "decode" else out)
            aux = moe_aux
        return x, aux, new_cache

    def _scan_layers(self, params, x, positions, mode: str,
                     cache=None, cur_len=None):
        """Scan the stacked layer groups. Returns (x, aux, caches).

        Train and prefill return each layer's new cache as the scan's
        outputs. Decode carries the stacked serve ``cache`` beside ``x``
        instead: each layer writes its new KV row (or Mamba state) into it
        in place and reads its own slice back, so a step does not rebuild
        the whole cache; its aux is None."""
        cfg = self.cfg
        period = cfg.scan_period
        groups = jax.tree.leaves(params["layers"])[0].shape[0]

        def group_params(g):
            return jax.tree.map(lambda t: t[g], params["layers"])

        if mode == "decode":
            def decode_fn(carry, xs):
                x, cache = carry
                gp, g = xs
                cache = dict(cache)
                for pos in range(period):
                    key = f"pos{pos}"
                    x, _, cache[key] = self._run_block(
                        gp[key], x, pos, None, mode, cache=cache[key],
                        cur_len=cur_len, layer=g)
                return (x, cache), None

            carry = (x, cache)
            if cfg.scan_layers:
                carry, _ = jax.lax.scan(
                    decode_fn, carry, (params["layers"], jnp.arange(groups)))
            else:
                for g in range(groups):
                    carry, _ = decode_fn(carry, (group_params(g), g))
            x, cache = carry
            return x, None, cache

        def group_fn(x, gp):
            auxes, ncaches = [], {}
            for pos in range(period):
                x, aux, nc = self._run_block(gp[f"pos{pos}"], x, pos,
                                             positions, mode)
                auxes.append(aux)
                if mode != "train":       # train never materializes caches
                    ncaches[f"pos{pos}"] = nc
            aux = jax.tree.map(lambda *a: sum(a), *auxes)
            return x, (aux, ncaches)

        fn = group_fn
        if cfg.remat and mode == "train":
            policy = (jax.checkpoint_policies.dots_saveable
                      if cfg.remat_policy == "dots"
                      else jax.checkpoint_policies.nothing_saveable)
            fn = jax.checkpoint(group_fn, policy=policy)

        if cfg.scan_layers:
            x, (aux, caches) = jax.lax.scan(fn, x, params["layers"])
            aux = jax.tree.map(jnp.sum, aux)
            return x, aux, caches
        # Unrolled path (dry-run cost extrapolation / tiny models): walk the
        # stacked groups in Python, then restack outputs like scan would.
        auxes, caches_list = [], []
        for g in range(groups):
            x, (aux, ncache) = fn(x, group_params(g))
            auxes.append(aux)
            caches_list.append(ncache)
        aux = jax.tree.map(lambda *a: jnp.sum(jnp.stack(a)), *auxes)
        caches = jax.tree.map(lambda *c: jnp.stack(c), *caches_list) \
            if caches_list and jax.tree.leaves(caches_list[0]) else {}
        return x, aux, caches

    # ---------------- public entry points -----------------------------------
    def _backbone(self, params, batch):
        """Embed → layers → final norm. Returns (hidden, aux, mask)."""
        x, mask = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux, _ = self._scan_layers(params, x, positions, "train")
        return layers.rms_norm(x, params["final_norm"]), aux, mask

    def _forward_full(self, params, batch):
        x, aux, mask = self._backbone(params, batch)
        logits = x @ params["lm_head"]
        logits = shard(logits, self.rules, "batch", "seq", "vocab",
                       mesh=self.mesh)
        return logits, aux, mask

    def forward(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        logits, aux, _ = self._forward_full(params, batch)
        return logits, aux

    def _ce_terms(self, logits, labels, mask):
        """(Σ masked CE, Σ masked logz², Σ mask) in fp32, padding masked."""
        cfg = self.cfg
        lg = logits.astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            col = jnp.arange(cfg.padded_vocab)
            lg = jnp.where(col < cfg.vocab_size, lg, -1e30)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        return (((logz - gold) * mask).sum(),
                ((logz * mask) ** 2).sum(), mask.sum())

    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        x, aux, x_mask = self._backbone(params, batch)
        B, S, _ = x.shape
        labels = self._full_labels(batch, S)

        if cfg.loss_chunks:
            # Chunked CE: per-chunk logits live only inside a checkpointed
            # region (recomputed in backward) — the full (B,S,V) tensor
            # never reaches HBM. Python-unrolled so HLO cost accounting
            # stays exact (scan bodies are billed once by XLA).
            n = cfg.loss_chunks
            C = -(-S // n)

            def chunk_terms(xc, lc, mc):
                logits = xc @ params["lm_head"]
                return self._ce_terms(logits, lc, mc)

            chunk_fn = jax.checkpoint(chunk_terms)
            ce_sum = z_sum = m_sum = 0.0
            for i in range(n):
                sl = slice(i * C, min((i + 1) * C, S))
                c, z, m = chunk_fn(x[:, sl], labels[:, sl], x_mask[:, sl])
                ce_sum, z_sum, m_sum = ce_sum + c, z_sum + z, m_sum + m
        else:
            logits = x @ params["lm_head"]
            logits = shard(logits, self.rules, "batch", "seq", "vocab",
                           mesh=self.mesh)
            ce_sum, z_sum, m_sum = self._ce_terms(logits, labels, x_mask)

        denom = jnp.maximum(m_sum, 1.0)
        loss = ce_sum / denom
        z_loss = 1e-4 * z_sum / denom
        total = loss + z_loss
        if cfg.moe is not None or cfg.family == "hybrid":
            total = total + 1e-2 * aux["load_balance"] + aux["router_z"]
        metrics = {"ce_loss": loss, "z_loss": z_loss, **aux}
        return total, metrics

    # ---------------- serving -----------------------------------------------
    def _cache_len(self, max_len: int) -> int:
        w = self.cfg.attn_window
        return min(w, max_len) if w is not None else max_len

    def init_cache(self, batch_size: int, max_len: int, abstract=False):
        """Zero (or abstract) serve cache matching the layer pattern."""
        cfg = self.cfg
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        C = self._cache_len(max_len)
        groups = cfg.num_layers // cfg.scan_period
        dt = jnp.dtype(cfg.param_dtype)

        def make(shape, dtype):
            if abstract:
                return jax.ShapeDtypeStruct(shape, dtype)
            return jnp.zeros(shape, dtype)

        cache = {}
        for pos in range(cfg.scan_period):
            mixer, _ = cfg.layer_kinds(pos)
            if mixer == "attn":
                if cfg.kv_cache_dtype == "int8":
                    cache[f"pos{pos}"] = {"attn": blocks.QuantAttnCache(
                        k=make((groups, batch_size, C, kv, hd), jnp.int8),
                        v=make((groups, batch_size, C, kv, hd), jnp.int8),
                        k_scale=make((groups, batch_size, C, kv),
                                     jnp.float32),
                        v_scale=make((groups, batch_size, C, kv),
                                     jnp.float32))}
                    continue
                cache[f"pos{pos}"] = {"attn": AttnCache(
                    k=make((groups, batch_size, C, kv, hd), dt),
                    v=make((groups, batch_size, C, kv, hd), dt))}
            else:
                d_in, H, P, N = mamba_dims(cfg)
                cache[f"pos{pos}"] = {"mamba": MambaCache(
                    conv_x=make((groups, batch_size, 3, d_in), dt),
                    conv_b=make((groups, batch_size, 3, N), dt),
                    conv_c=make((groups, batch_size, 3, N), dt),
                    ssm=make((groups, batch_size, H, P, N), jnp.float32))}
        return cache

    def cache_specs(self):
        """PartitionSpecs congruent with init_cache output."""
        r = self.rules
        cfg = self.cfg
        specs = {}
        for pos in range(cfg.scan_period):
            mixer, _ = cfg.layer_kinds(pos)
            if mixer == "attn":
                kv_spec = r.spec("layers", "batch", "kv_seq", None, None)
                if cfg.kv_cache_dtype == "int8":
                    specs[f"pos{pos}"] = {"attn": blocks.QuantAttnCache(
                        k=kv_spec, v=kv_spec,
                        k_scale=r.spec("layers", "batch", "kv_seq", None),
                        v_scale=r.spec("layers", "batch", "kv_seq", None))}
                    continue
                specs[f"pos{pos}"] = {"attn": AttnCache(k=kv_spec,
                                                        v=kv_spec)}
            else:
                specs[f"pos{pos}"] = {"mamba": MambaCache(
                    conv_x=r.spec("layers", "batch", None, "heads"),
                    conv_b=r.spec("layers", "batch", None, None),
                    conv_c=r.spec("layers", "batch", None, None),
                    ssm=r.spec("layers", "batch", "heads", None, None))}
        return specs

    @jax.named_scope("prefill")
    def prefill(self, params, batch, max_len: int):
        """Full-context forward; returns (last_logits, cache, cur_len)."""
        cfg = self.cfg
        x, _ = self._embed_inputs(params, batch)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, _, raw_caches = self._scan_layers(params, x, positions, "prefill")

        # convert per-layer prefill KV into serve layout (ring for SWA)
        def convert(sub):
            out = {}
            for k, v in sub.items():
                if "attn" in v:
                    out[k] = {"attn": blocks.attn_prefill_cache(
                        v["attn"], cfg, S, max_len)}
                else:
                    out[k] = v
            return out

        cache = convert(raw_caches)
        xn = layers.rms_norm(x[:, -1], params["final_norm"])
        logits = (xn @ params["lm_head"])[:, :cfg.vocab_size]
        return logits, cache, jnp.asarray(S, jnp.int32)

    @jax.named_scope("decode_step")
    def decode_step(self, params, token: jnp.ndarray, cache,
                    cur_len: jnp.ndarray):
        """One serve step: embed token (B,), walk layers, update cache."""
        cfg = self.cfg
        # The 1-D decode token stream is controller traffic too: one
        # scheduler batch through mc_embed, not a raw bypassing take.
        x = layers.mc_embed(params["embed"]["table"], token, cfg.mc)
        x, _, new_cache = self._scan_layers(params, x, None, "decode",
                                            cache=cache, cur_len=cur_len)
        xn = layers.rms_norm(x, params["final_norm"])
        logits = xn @ params["lm_head"]
        logits = shard(logits, self.rules, "batch", "vocab", mesh=self.mesh)
        return logits[:, :cfg.vocab_size], new_cache


def build_lm(cfg: ArchConfig, mesh=None, *, global_batch: int = 0,
             moe_strategy: str = "tp") -> LM:
    if moe_strategy == "ep":
        if mesh is None or cfg.moe is None:
            raise ValueError("moe_strategy='ep' needs a mesh and an MoE "
                             "architecture")
        tp = mesh.shape["model"]
        if cfg.moe.num_experts % tp or cfg.moe.num_shared_experts:
            raise ValueError(
                f"EP dispatch needs num_experts % {tp} == 0 and no shared "
                f"experts (got {cfg.moe.num_experts}e/"
                f"{cfg.moe.num_shared_experts}shared); use 'tp'")
    rules = make_rules(mesh, global_batch=global_batch,
                       moe_strategy=moe_strategy,
                       num_kv_heads=cfg.num_kv_heads,
                       num_heads=cfg.num_heads)
    return LM(cfg=cfg, rules=rules, mesh=mesh, moe_strategy=moe_strategy)
