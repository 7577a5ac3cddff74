"""Cross-path consistency: decode-with-cache == cache-free forward,
chunked SSD == stepwise recurrence, flash == naive attention, ring-buffer
SWA cache == dense windowed attention."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.core.config import MemoryControllerConfig, SchedulerConfig
from repro.models import build_lm
from repro.models.layers import (decode_attention, flash_attention,
                                 mc_embed, mc_scatter)
from repro.models.params import mamba_dims

DECODABLE = [a for a in ARCH_IDS if a != "hubert_xlarge"]


def _f32(cfg):
    reps = {"param_dtype": "float32"}
    if cfg.moe is not None:
        reps["moe"] = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    return dataclasses.replace(cfg, **reps)


@pytest.mark.parametrize("arch", DECODABLE)
def test_decode_matches_full_forward(arch, key):
    cfg = _f32(get_arch(arch, smoke=True))
    lm = build_lm(cfg)
    params = lm.init(key)
    B, S = 2, 32
    if cfg.modality == "vision_text":
        st = S + 1 - cfg.num_vision_tokens
        vis = jax.random.normal(jax.random.key(7),
                                (B, cfg.num_vision_tokens,
                                 cfg.frontend_dim), jnp.float32)
        toks = jax.random.randint(key, (B, st), 0, cfg.vocab_size)
        full = {"tokens": toks, "vision_embeds": vis}
        pre = {"tokens": toks[:, :-1], "vision_embeds": vis}
        last = toks[:, -1]
    else:
        toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
        full = {"tokens": toks}
        pre = {"tokens": toks[:, :S]}
        last = toks[:, S]
    want = lm.forward(params, full)[0][:, -1, :cfg.vocab_size]
    _, cache, cur = lm.prefill(params, pre, max_len=S + 8)
    got, _ = lm.decode_step(params, last, cache, cur)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-3, rtol=1e-3)


@pytest.mark.parametrize("arch", ["mamba2_2p7b", "h2o_danube_1p8b",
                                  "mixtral_8x7b"])
def test_multi_step_decode_matches_full(arch, key):
    """Decode 4 tokens sequentially; each must match the cache-free model."""
    cfg = _f32(get_arch(arch, smoke=True))
    lm = build_lm(cfg)
    params = lm.init(key)
    B, S, K = 2, 24, 4
    toks = jax.random.randint(key, (B, S + K), 0, cfg.vocab_size)
    _, cache, cur = lm.prefill(params, {"tokens": toks[:, :S]},
                               max_len=S + K + 8)
    for t in range(K):
        want = lm.forward(
            params, {"tokens": toks[:, :S + t + 1]})[0][:, -1,
                                                        :cfg.vocab_size]
        got, cache = lm.decode_step(params, toks[:, S + t], cache, cur)
        cur = cur + 1
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-3, rtol=1e-3,
                                   err_msg=f"token {t}")


def _naive_attention(q, k, v, causal=True, window=None):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qg, k) / np.sqrt(hd)
    pos_q = jnp.arange(S)[:, None]
    pos_k = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool) if not causal else pos_k <= pos_q
    if window is not None:
        mask &= pos_k > pos_q - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", p, v)
    return o.reshape(B, S, H, hd)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(8, 16), (64, 64), (16, 128)])
def test_flash_matches_naive(causal, blocks, key):
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out = flash_attention(q, k, v, causal=causal, q_block=blocks[0],
                          kv_block=blocks[1])
    want = _naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [4, 16, 64])
def test_flash_swa_matches_naive(window, key):
    B, S, H, KV, hd = 1, 48, 4, 4, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    out = flash_attention(q, k, v, causal=True, window=window,
                          q_block=16, kv_block=16)
    want = _naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_matches_last_row_of_full(key):
    B, S, H, KV, hd = 2, 33, 4, 2, 8
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    full = _naive_attention(q, k, v, causal=True)[:, -1]
    got = decode_attention(q[:, -1], k, v,
                           jnp.ones((B, S), bool))
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_ssd_chunk_size_invariance(key):
    """Chunked SSD must give identical results for any chunk size."""
    from repro.models import blocks as blk
    cfg = _f32(get_arch("mamba2_2p7b", smoke=True))
    lm = build_lm(cfg)
    params = lm.init(key)
    p = jax.tree.map(lambda t: t[0], params["layers"]["pos0"]["mamba"])
    x = jax.random.normal(key, (2, 32, cfg.d_model), jnp.float32)
    outs = []
    for chunk in (4, 8, 16, 32):
        c = dataclasses.replace(cfg,
                                ssm=dataclasses.replace(cfg.ssm,
                                                        chunk=chunk))
        out, _ = blk.mamba_forward(p, x, c, lm.rules, None)
        outs.append(np.asarray(out, np.float32))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-4, rtol=1e-4)


def _mamba_layer(key, cfg):
    from repro.models import blocks as blk
    lm = build_lm(cfg)
    params = lm.init(key)
    return blk, lm, jax.tree.map(lambda t: t[0],
                                 params["layers"]["pos0"]["mamba"])


@pytest.mark.parametrize("S", [32, 40, 7])
def test_chunked_ssd_matches_stepwise_recurrence(S, key):
    """The chunked forward (smoke chunk 16) against the O(1) decode step run
    token by token from a zero cache: a chunk multiple, a padded length,
    and one shorter than a chunk. Output and every cache leaf agree."""
    cfg = _f32(get_arch("mamba2_2p7b", smoke=True))
    blk, lm, p = _mamba_layer(key, cfg)
    B = 2
    x = jax.random.normal(jax.random.key(S), (B, S, cfg.d_model), jnp.float32)
    out, cache = blk.mamba_forward(p, x, cfg, lm.rules, None)

    d_in, H, P, N = mamba_dims(cfg)
    step = blk.MambaCache(conv_x=jnp.zeros((1, B, 3, d_in)),
                          conv_b=jnp.zeros((1, B, 3, N)),
                          conv_c=jnp.zeros((1, B, 3, N)),
                          ssm=jnp.zeros((1, B, H, P, N)))
    outs = []
    for t in range(S):
        o, step = blk.mamba_decode(p, x[:, t], step, 0, cfg, lm.rules, None)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.stack(outs, 1)),
                               atol=2e-5, rtol=2e-5)
    for name in ("ssm", "conv_x", "conv_b", "conv_c"):
        np.testing.assert_allclose(np.asarray(getattr(cache, name)),
                                   np.asarray(getattr(step, name)[0]),
                                   atol=2e-5, rtol=2e-5, err_msg=name)


def test_ssd_gradient_is_finite_where_the_decay_overflows(key):
    """With A = -e^3 and dt near 3, exp(cum_l - cum_m) above the chunk's
    diagonal overflows; masking it before exp keeps every gradient finite."""
    cfg = _f32(get_arch("mamba2_2p7b", smoke=True))
    blk, lm, p = _mamba_layer(key, cfg)
    p = dict(p, a_log=jnp.full_like(p["a_log"], 3.0),
             dt_bias=jnp.full_like(p["dt_bias"], 3.0))
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model), jnp.float32)
    grads = jax.grad(lambda p: jnp.sum(
        blk.mamba_forward(p, x, cfg, lm.rules, None)[0]))(p)
    for name, g in grads.items():
        assert np.all(np.isfinite(np.asarray(g))), name


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_ssd_prefill_moves_no_float32_copy_of_x(key):
    """At smoke widths (bf16 parameters) the forward holds no float32
    transpose of an x-sized array and no scan over an x-sized input: x
    stays in its own layout and dtype around the chunk scan."""
    cfg = get_arch("mamba2_2p7b", smoke=True)
    blk, lm, p = _mamba_layer(key, cfg)
    B, S = 2, 32
    x = jnp.zeros((B, S, cfg.d_model), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda p, x: blk.mamba_forward(p, x, cfg, lm.rules, None))(p, x)
    x_size = B * S * mamba_dims(cfg)[0]
    for e in _eqns(jaxpr.jaxpr):
        if e.primitive.name == "transpose":
            aval = e.invars[0].aval
            assert not (aval.dtype == jnp.float32 and aval.size == x_size), \
                f"float32 transpose of {aval.shape}"
        if e.primitive.name == "scan":
            first = e.params["num_consts"] + e.params["num_carry"]
            for v in e.invars[first:]:
                assert v.aval.size != x_size, f"scan over {v.aval.shape}"


@pytest.mark.parametrize("sched", [True, False])
def test_mc_scatter_matches_naive_update(sched, key, rng):
    """Embedding-gradient scatter through the controller == table.at[].add,
    with or without the scheduler (value-semantics contract)."""
    mc = MemoryControllerConfig(scheduler=SchedulerConfig(enabled=sched))
    table = jnp.asarray(rng.standard_normal((96, 16)), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, 96, (2, 24)), jnp.int32)
    grads = jnp.asarray(rng.standard_normal((2, 24, 16)), jnp.float32)
    out = mc_scatter(table, tokens, grads, mc, mode="add")
    naive = table.at[tokens.reshape(-1)].add(grads.reshape(-1, 16))
    np.testing.assert_allclose(np.asarray(out), np.asarray(naive),
                               rtol=1e-4, atol=1e-5)
    # round trip with the read path: an updated row is what mc_embed sees
    re_read = mc_embed(out, tokens, mc)
    np.testing.assert_allclose(np.asarray(re_read), np.asarray(out[tokens]),
                               rtol=1e-6)


def test_lm_embedding_grad_update(key, rng):
    cfg = _f32(get_arch("yi_34b", smoke=True))
    lm = build_lm(cfg)
    params = lm.init(key)
    V = params["embed"]["table"].shape[0]
    tokens = jnp.asarray(rng.integers(0, V, (2, 8)), jnp.int32)
    grads = jnp.asarray(
        rng.standard_normal((2, 8, cfg.d_model)), jnp.float32)
    new_params = lm.embedding_grad_update(params, tokens, grads, lr=0.5)
    table = params["embed"]["table"]
    expect = table.at[tokens.reshape(-1)].add(
        (-0.5 * grads.reshape(-1, cfg.d_model)).astype(table.dtype))
    np.testing.assert_allclose(np.asarray(new_params["embed"]["table"]),
                               np.asarray(expect), rtol=1e-4, atol=1e-5)
    # only the embedding leaf changed
    assert new_params["lm_head"] is params["lm_head"]
