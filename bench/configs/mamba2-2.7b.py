"""Plain float32 reference of Mamba-2 (SSD, arXiv:2405.21060) as this repo's
registry builds it.

Imports nothing of the program. ``weights`` draws the same values the
program's ``Server`` draws from ``jax.random.key(weights_key)``.
``logits`` runs a batch of sequences in float32 with ``Precision.HIGHEST``
matmuls, one layer at a time, and computes the state-space mixer as its
plain recurrence over time (the program computes it in chunks):

    n = rmsnorm(x); z, u = n·Wzx; b, c = n·Wbc; dt = softplus(n·Wdt + dt_bias)
    u, b, c = silu(causal_conv4(u)), silu(causal_conv4(b)), silu(causal_conv4(c))
    h_t = exp(dt_t * a) h_{t-1} + dt_t u_t b_t^T,   a = -exp(a_log)
    y_t = h_t c_t + d_skip * u_t
    x += Wo · rmsnorm(y * silu(z))

Departures of the registry's block from the paper's, kept here because
the reference follows the model as run: the three convolutions have no
bias and run on u, b and c separately; dt is its own projection of the
normed input; there is no MLP.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict):
    d_in = cfg["ssm_expand"] * cfg["d_model"]
    return d_in, d_in // cfg["ssm_head_dim"], cfg["ssm_head_dim"], \
        cfg["ssm_d_state"]


def decls(cfg: dict) -> dict:
    """{path: (shape, init, fan_in)} nested like the program's tree."""
    d, L = cfg["d_model"], cfg["num_layers"]
    d_in, nh, _, n = dims(cfg)

    def w(shape, init="normal", fan_in=None):
        if init == "normal" and fan_in is None:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return ((L,) + shape, init, fan_in)

    return {
        "embed": {"table": ((cfg["padded_vocab"], d), "normal", d)},
        "final_norm": ((d,), "ones", None),
        "layers": {"pos0": {"mamba": {
            "ln": w((d,), "ones"),
            "w_zx": w((d, 2 * d_in)),
            "w_bc": w((d, 2 * n)),
            "w_dt": w((d, nh)),
            "dt_bias": w((nh,), "dt_bias"),
            "a_log": w((nh,), "ssm_a"),
            "d_skip": w((nh,), "ones"),
            "conv_x": w((4, d_in), "normal", 4),
            "conv_b": w((4, n), "normal", 4),
            "conv_c": w((4, n), "normal", 4),
            "gated_ln": w((d_in,), "ones"),
            "wo": w((d_in, d)),
        }}},
        "lm_head": ((d, cfg["padded_vocab"]), "normal", d),
    }


def draw(decl, key):
    shape, init, fan_in = decl
    if init == "ones":
        return jnp.ones(shape, jnp.bfloat16)
    if init == "normal":
        scale = 1.0 / math.sqrt(max(1, fan_in))
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(jnp.bfloat16)
    if init == "ssm_a":        # A in [1, 16], stored as its log
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(jnp.float32)
    if init == "dt_bias":      # inverse softplus of dt ~ LogUniform[1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32)
    raise ValueError(init)


def weights(cfg: dict, draw_leaf=draw):
    leaves, treedef = jax.tree.flatten(
        decls(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(cfg["weights_key"]), len(leaves))
    return jax.tree.unflatten(
        treedef, [draw_leaf(d, k) for d, k in zip(leaves, keys)])


def lower(x, precision: str):
    """Matmul operands as the precision computes them: float32 as is, or
    rounded to float8 e4m3 with one scale per tensor (the control)."""
    if precision == "float32":
        return x
    if precision == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(precision)


def mm(a, b, precision):
    return jnp.matmul(lower(a, precision), lower(b.astype(jnp.float32),
                                                precision), precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def conv4(u, w):
    """Causal depthwise convolution, kernel 4, zero history, then silu."""
    S = u.shape[0]
    full = jnp.concatenate([jnp.zeros((3, u.shape[1]), u.dtype), u])
    w = w.astype(jnp.float32)
    return jax.nn.silu(sum(full[i:i + S] * w[i] for i in range(4)))


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer(x, layers, i, cfg_items, precision):
    """One layer over a batch of sequences x (B, S, d_model)."""
    return jax.vmap(lambda s: _layer_one(s, layers, i, cfg_items,
                                         precision))(x)


def _layer_one(x, layers, i, cfg_items, precision):
    cfg = dict(cfg_items)
    p = jax.tree.map(lambda t: t[i], layers["mamba"])
    d_in, nh, hp, n = (cfg["d_in"], cfg["heads"], cfg["head_dim"],
                       cfg["d_state"])
    xn = rmsnorm(x, p["ln"], cfg["norm_eps"])
    zx = mm(xn, p["w_zx"], precision)
    z, u = zx[:, :d_in], zx[:, d_in:]
    bc = mm(xn, p["w_bc"], precision)
    b, c = bc[:, :n], bc[:, n:]
    dt = jax.nn.softplus(mm(xn, p["w_dt"], precision) + p["dt_bias"])
    u, b, c = conv4(u, p["conv_x"]), conv4(b, p["conv_b"]), \
        conv4(c, p["conv_c"])
    a = -jnp.exp(p["a_log"])
    uh = u.reshape(-1, nh, hp)

    def step(h, inp):
        u_t, b_t, c_t, dt_t = inp
        h = (h * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * u_t)[:, :, None] * b_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", h, c_t, precision=HI)
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, n), jnp.float32),
                        (uh, b, c, dt))
    y = y + uh * p["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(-1, d_in) * jax.nn.silu(z)
    y = rmsnorm(y, p["gated_ln"], cfg["norm_eps"])
    return x + mm(y, p["wo"], precision)


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(x, final_norm, lm_head, cfg_items, precision):
    cfg = dict(cfg_items)
    xn = rmsnorm(x, final_norm, cfg["norm_eps"])
    return mm(xn, lm_head, precision)[..., :cfg["vocab_size"]]


def _items(cfg: dict) -> tuple:
    d_in, nh, hp, n = dims(cfg)
    return (("d_in", d_in), ("heads", nh), ("head_dim", hp), ("d_state", n),
            ("norm_eps", cfg["norm_eps"]), ("vocab_size", cfg["vocab_size"]))


def logits(w, cfg: dict, tokens: np.ndarray, positions: np.ndarray,
           precision: str = "float32") -> np.ndarray:
    """Float32 logits (B, P, vocab) of the sequences ``tokens`` (B, L) at
    ``positions`` (B, P). Each sequence runs on its own; padding to one
    length L is harmless after the last position asked for."""
    items = _items(cfg)
    x = jnp.take(w["embed"]["table"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        x = _layer(x, w["layers"]["pos0"], jnp.int32(i), items, precision)
    x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
    return np.asarray(_head(x, w["final_norm"], w["lm_head"], items,
                            precision))
