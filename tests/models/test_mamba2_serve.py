"""Mamba-2 on the serving path at smoke widths: the scopes its compiled
programs carry, the decode state ``Server`` counts, and left-padded
prefill followed by the donated decode against the model's own full
forward."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch import serve
from repro.launch.serve import Request, Server


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def test_prefill_and_decode_carry_the_mamba_scopes():
    server = Server("mamba2-2.7b", smoke=True)
    tokens = jnp.zeros((2, 20), jnp.int32)
    prefill = server._prefill.lower(server.params, {"tokens": tokens},
                                    28).compile()
    _, cache, cur = server._prefill(server.params, {"tokens": tokens}, 28)
    decode = server._decode.lower(server.params, jnp.zeros((2,), jnp.int32),
                                  cache, cur).compile()
    for compiled, top, scopes in (
            (prefill, "/prefill/", ("mamba_conv", "ssd_chunk_scan")),
            (decode, "jit(decode_step)/decode_step/",
             ("mamba_conv", "ssm_state_step"))):
        paths = _op_names(compiled)
        for scope in scopes:
            assert any(top in p and f"/{scope}/" in p for p in paths), scope


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "mamba2-2.7b"])
def test_state_bytes_are_the_cache_pytrees_bytes(arch):
    server = Server(arch, smoke=True)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, 200, n).astype(np.int32), m)
            for i, (n, m) in enumerate([(9, 3), (14, 5), (6, 2)])]
    stats = server.serve(reqs)
    cache = server.lm.init_cache(3, 14 + 5 + 8, abstract=True)
    want = sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache))
    assert stats.state_bytes == want > 0


def test_left_padded_prefill_then_donated_decode_matches_full_forward(
        monkeypatch):
    """Float32 weights through ``Server``'s own prefill and donated decode:
    the prompts are left-padded to 20 tokens (not a multiple of the smoke
    chunk of 16), and each step's logits agree with the full forward over
    the padded sequence so far."""
    cfg = dataclasses.replace(get_arch("mamba2-2.7b", smoke=True),
                              param_dtype="float32")
    monkeypatch.setattr(serve, "get_arch", lambda name, smoke=False: cfg)
    server = Server("mamba2-2.7b", smoke=True)
    rng = np.random.default_rng(4)
    S, steps = 20, 5
    prompts = np.stack([np.pad(rng.integers(0, cfg.vocab_size, n), (S - n, 0))
                        for n in (13, 20)]).astype(np.int32)
    fed = rng.integers(0, cfg.vocab_size, (2, steps)).astype(np.int32)
    logits, cache, cur = server._prefill(
        server.params, {"tokens": jnp.asarray(prompts)}, S + steps + 8)
    seq = prompts
    for t in range(steps + 1):
        want = server.lm.forward(server.params, {"tokens": jnp.asarray(seq)}
                                 )[0][:, -1, :cfg.vocab_size]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   atol=3e-3, rtol=1e-3, err_msg=f"step {t}")
        if t == steps:
            break
        old = cache
        logits, cache = server._decode(server.params,
                                       jnp.asarray(fed[:, t]), cache, cur)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old))
        cur = cur + 1
        seq = np.concatenate([seq, fed[:, t:t + 1]], axis=1)
