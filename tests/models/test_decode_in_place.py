"""The decode step's in-place cache update against the scan it replaced.

``LM.decode_step`` carries the stacked serve cache through the layer scan
and writes each layer's new KV row (or Mamba state) into it. Before, the
cache went into the scan as its ``xs``, and each layer returned its whole
updated cache, which the scan stacked again as its ``ys``. Both orders do
the same arithmetic, so logits and every cache leaf must agree exactly,
step after step, past the sliding window's wrap.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import build_lm, layers

S, STEPS = 6, 12        # 6 + 12 tokens wrap the smoke window of 8 slots

CASES = [("h2o_danube_1p8b", "param"), ("yi_34b", "param"),
         ("h2o_danube_1p8b", "int8"), ("qwen2_moe_a2p7b", "param"),
         ("mamba2_2p7b", "param"), ("jamba_v0p1_52b", "param")]


def one_slot_append(buf, new, slot, mc, axis=1, layer=None):
    """The append as it was: a one-slot update of the layer's own cache
    (here a stack of one, so ``layer`` is 0)."""
    return jax.lax.dynamic_update_slice_in_dim(buf, new[None], slot,
                                               axis + 1)


def xs_ys_decode_step(lm, params, token, cache, cur_len):
    """The decode step as the scan walked it before: the stacked cache is
    the scan's ``xs``, each layer returns its own slice updated, and the
    scan stacks the slices as its ``ys``. A layer's slice goes through the
    model's blocks as a stack of one; trace it with ``one_slot_append``
    in place of ``mc_kv_append``."""
    cfg = lm.cfg
    x = layers.mc_embed(params["embed"]["table"], token, cfg.mc)

    def group_fn(x, xs):
        gp, gcache = xs
        ncaches = {}
        for pos in range(cfg.scan_period):
            key = f"pos{pos}"
            one = jax.tree.map(lambda t: t[None], gcache[key])
            x, _, nc = lm._run_block(gp[key], x, pos, None, "decode",
                                     cache=one, cur_len=cur_len, layer=0)
            ncaches[key] = jax.tree.map(lambda t: t[0], nc)
        return x, ncaches

    x, new_cache = jax.lax.scan(group_fn, x, (params["layers"], cache))
    xn = layers.rms_norm(x, params["final_norm"])
    return (xn @ params["lm_head"])[:, :cfg.vocab_size], new_cache


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan", "unrolled"])
@pytest.mark.parametrize("arch,kv_dtype", CASES,
                         ids=[f"{a}-{d}" for a, d in CASES])
def test_in_place_decode_matches_xs_ys_scan(arch, kv_dtype, scan_layers,
                                            key, monkeypatch):
    cfg = dataclasses.replace(get_arch(arch, smoke=True),
                              param_dtype="float32", kv_cache_dtype=kv_dtype,
                              scan_layers=scan_layers)
    lm = build_lm(cfg)
    params = lm.init(key)
    toks = jax.random.randint(jax.random.key(3), (2, S + STEPS), 0,
                              cfg.vocab_size)
    _, cache, cur = lm.prefill(params, {"tokens": toks[:, :S]},
                               max_len=S + STEPS)
    want_cache = jax.tree.map(jnp.copy, cache)
    step = jax.jit(lm.decode_step, donate_argnums=(2,))
    with monkeypatch.context() as m:
        m.setattr(layers, "mc_kv_append", one_slot_append)
        ref = jax.jit(lambda p, t, c, n: xs_ys_decode_step(lm, p, t, c, n)
                      ).lower(params, toks[:, S], want_cache, cur).compile()
    for t in range(STEPS):
        got, cache = step(params, toks[:, S + t], cache, cur)
        want, want_cache = ref(params, toks[:, S + t], want_cache, cur)
        cur = cur + 1
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"logits, step {t}")
        assert jax.tree.structure(cache) == jax.tree.structure(want_cache)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(cache),
                                jax.tree.leaves(want_cache)):
            assert a.shape == b.shape and a.dtype == b.dtype, path
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{jax.tree_util.keystr(path)}, step {t}")
