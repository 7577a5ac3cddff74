"""Plain float32 reference of a dense decoder with sliding-window attention
(h2o-danube, arXiv:2401.16818, as this repo's registry builds it).

Imports nothing of the program. ``weights`` draws the same bf16 values the
program's ``Server`` draws from ``jax.random.key(weights_key)``: the
declaration order, the key split and each leaf's scale are written out
here. ``logits`` runs a batch of sequences through the layers in float32 with
``Precision.HIGHEST`` matmuls, one layer at a time, and returns the logits
at the positions asked for.

The model, as run: token embedding; per layer ``x += Wo·attn(rope(Wq·n1),
rope(Wk·n1), Wv·n1)`` with ``n1 = rmsnorm(x)``, grouped-query attention,
causal with a sliding window (key position > query position - window),
then ``x += Wd·(silu(Wg·n2) * (Wu·n2))``; final RMSNorm; untied LM head.
RoPE rotates the two halves of each head (not interleaved pairs).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# Weights: the program's declaration tree, drawn leaf by leaf like it does
# --------------------------------------------------------------------------

def decls(cfg: dict) -> dict:
    """{path: (shape, init, fan_in)} nested like the program's tree."""
    d, h, kv, hd, f = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    n = cfg["num_layers"]

    def w(shape, fan_in=None):
        return ((n,) + shape, "normal", fan_in or shape[-2])

    return {
        "embed": {"table": ((cfg["padded_vocab"], d), "normal", d)},
        "final_norm": ((d,), "ones", None),
        "layers": {"pos0": {
            "attn": {"ln": ((n, d), "ones", None),
                     "wq": w((d, h * hd)), "wk": w((d, kv * hd)),
                     "wv": w((d, kv * hd)), "wo": w((h * hd, d))},
            "mlp": {"ln": ((n, d), "ones", None),
                    "w_gate": w((d, f)), "w_up": w((d, f)),
                    "w_down": w((f, d))},
        }},
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
    raise ValueError(init)


def weights(cfg: dict, draw_leaf=draw):
    tree = decls(cfg)
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(cfg["weights_key"]), len(leaves))
    return jax.tree.unflatten(
        treedef, [draw_leaf(d, k) for d, k in zip(leaves, keys)])


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

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


def rope(x, theta):
    """x: (S, heads, hd); rotates the halves by position."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _layer(x, layers, i, cfg_items, precision):
    """One layer over a batch of sequences x (B, S, d_model)."""
    return jax.vmap(lambda s: _layer_one(s, layers, i, cfg_items,
                                         precision))(x)


def _layer_one(x, layers, i, cfg_items, precision):
    cfg = dict(cfg_items)
    p = jax.tree.map(lambda t: t[i], layers)
    S = x.shape[0]
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    a = p["attn"]
    n1 = rmsnorm(x, a["ln"], eps)
    q = rope(mm(n1, a["wq"], precision).reshape(S, h, hd), cfg["rope_theta"])
    k = rope(mm(n1, a["wk"], precision).reshape(S, kv, hd), cfg["rope_theta"])
    v = mm(n1, a["wv"], precision).reshape(S, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)          # query head i reads kv i//G
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", lower(q, precision), lower(k, precision),
                   precision=HI) / math.sqrt(hd)
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if cfg["attn_window"]:
        mask &= pos[None, :] > pos[:, None] - cfg["attn_window"]
    s = jnp.where(mask[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), lower(v, precision),
                   precision=HI)
    x = x + mm(o.reshape(S, h * hd), a["wo"], precision)
    m = p["mlp"]
    n2 = rmsnorm(x, m["ln"], eps)
    g = jax.nn.silu(mm(n2, m["w_gate"], precision)) * mm(n2, m["w_up"],
                                                         precision)
    return x + mm(g, m["w_down"], precision)


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _head(x, final_norm, lm_head, cfg_items, precision):
    cfg = dict(cfg_items)
    xn = rmsnorm(x, final_norm, cfg["norm_eps"])
    return mm(xn, lm_head, precision)[..., :cfg["vocab_size"]]


def _items(cfg: dict) -> tuple:
    keys = ("num_heads", "num_kv_heads", "head_dim", "norm_eps",
            "rope_theta", "attn_window", "vocab_size")
    return tuple((k, cfg[k]) for k in keys)


def logits(w, cfg: dict, tokens: np.ndarray, positions: np.ndarray,
           precision: str = "float32") -> np.ndarray:
    """Float32 logits (B, P, vocab) of the sequences ``tokens`` (B, L) at
    ``positions`` (B, P). Each sequence runs on its own; padding to one
    length L is harmless after the last position asked for."""
    items = _items(cfg)
    x = jnp.take(w["embed"]["table"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    layers = w["layers"]["pos0"]
    for i in range(cfg["num_layers"]):
        x = _layer(x, layers, jnp.int32(i), items, precision)
    x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
    return np.asarray(_head(x, w["final_norm"], w["lm_head"], items,
                            precision))
