"""Optimizer substrate: AdamW behaviour, schedule, compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.adamw import (OptimizerConfig, adamw_update, init_opt_state,
                               lr_schedule)
from repro.optim.compress import compress_int8, decompress_int8


def test_adamw_optimizes_quadratic(key):
    params = {"w": jax.random.normal(key, (8,))}
    target = jnp.arange(8.0)
    cfg = OptimizerConfig(peak_lr=0.1, warmup_steps=1, total_steps=200,
                          weight_decay=0.0)
    opt = init_opt_state(params)

    def loss_fn(p):
        return jnp.sum((p["w"] - target) ** 2)

    loss0 = float(loss_fn(params))
    for _ in range(100):
        g = jax.grad(loss_fn)(params)
        params, opt, _ = adamw_update(g, opt, params, cfg)
    assert float(loss_fn(params)) < 0.1 * loss0
    assert int(opt["step"]) == 100


def test_grad_clipping_bounds_update():
    params = {"w": jnp.zeros((4,))}
    cfg = OptimizerConfig(peak_lr=1e-2, warmup_steps=0, clip_norm=1.0)
    opt = init_opt_state(params)
    huge = {"w": jnp.full((4,), 1e9)}
    p2, _, metrics = adamw_update(huge, opt, params, cfg)
    assert float(metrics["grad_norm"]) > 1e9 - 1
    assert np.isfinite(np.asarray(p2["w"])).all()
    assert float(jnp.max(jnp.abs(p2["w"]))) < 1.0


def test_lr_schedule_shape():
    cfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    lrs = [float(lr_schedule(jnp.asarray(s), cfg)) for s in range(101)]
    assert lrs[0] == 0.0
    np.testing.assert_allclose(lrs[10], 1e-3, rtol=1e-5)
    assert all(a >= b - 1e-12 for a, b in zip(lrs[10:], lrs[11:]))
    np.testing.assert_allclose(lrs[100], 1e-4, rtol=1e-3)


def test_weight_decay_only_on_matrices(key):
    w2 = jax.random.normal(key, (4, 4)) * 10
    b1 = jax.random.normal(key, (4,)) * 10
    params = {"w": w2, "b": b1}
    cfg = OptimizerConfig(peak_lr=1e-2, warmup_steps=0, weight_decay=1.0)
    opt = init_opt_state(params)
    zero_g = jax.tree.map(jnp.zeros_like, params)
    p2, _, _ = adamw_update(zero_g, opt, params, cfg)
    assert float(jnp.max(jnp.abs(p2["b"] - b1))) < 1e-6       # no decay
    assert float(jnp.max(jnp.abs(p2["w"] - w2))) > 1e-4       # decayed


def test_int8_compression_error_bounded(key):
    g = jax.random.normal(key, (1024,)) * 3.0
    q, scale = compress_int8(g)
    back = decompress_int8(q, scale)
    assert q.dtype == jnp.int8
    max_err = float(jnp.max(jnp.abs(back - g)))
    assert max_err <= float(scale) / 2 + 1e-6    # half-ulp rounding bound


def test_compressed_psum_error_feedback_unbiased():
    """Over repeated steps with error feedback, the accumulated applied
    gradient tracks the true gradient (bias vanishes)."""
    from repro.optim.compress import compressed_psum, init_residuals

    g = {"w": jnp.linspace(-2.0, 2.0, 64)}
    res = init_residuals(g)
    applied = jnp.zeros((64,))

    def one(axis_g, axis_r):
        # single-device psum via shard_map over a trivial mesh
        from repro.models.sharding import make_mesh
        mesh = make_mesh((1,), ("pod",))
        f = jax.shard_map(
            lambda gg, rr: compressed_psum(gg, rr, "pod", mode="int8"),
            mesh=mesh, in_specs=(jax.P(), jax.P()),
            out_specs=(jax.P(), jax.P()))
        return f(axis_g, axis_r)

    for _ in range(50):
        out, res = one(g, res)
        applied = applied + out["w"]
    want = g["w"] * 50
    # relative error of the running sum shrinks well below one quant step
    np.testing.assert_allclose(np.asarray(applied), np.asarray(want),
                               atol=0.05)
