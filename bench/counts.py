"""Operations and bytes that the work needs, from the configuration's sizes.

These count what the algorithm needs, not what the program does today:
a prompt's left padding, the decode steps a request no longer needs, the
cache slots beyond a request's own context and repeated feature rows are
not counted. A later change that stops doing such work therefore still
reads under 100% of the chip's peak.

``cfg`` is a configuration file's dict (``configs/<config>.json``).
"""

from __future__ import annotations

from typing import Iterator, Sequence, Tuple

BF16 = 2
F32 = 4


# --------------------------------------------------------------------------
# Decoder models (family "dense": attention + SwiGLU; "ssm": Mamba-2 SSD)
# --------------------------------------------------------------------------

def _mamba_dims(cfg: dict) -> Tuple[int, int, int, int]:
    d_in = cfg["ssm_expand"] * cfg["d_model"]
    return d_in, d_in // cfg["ssm_head_dim"], cfg["ssm_head_dim"], \
        cfg["ssm_d_state"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that multiply every token."""
    d = cfg["d_model"]
    if cfg["family"] == "ssm":
        d_in, heads, _, n = _mamba_dims(cfg)
        return d * (2 * d_in + 2 * n + heads) + d_in * d
    h, kv, hd, f = (cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                    cfg["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def layer_other_bytes(cfg: dict) -> int:
    """Bytes of one layer's small weights (norms, convolution, SSM)."""
    d = cfg["d_model"]
    if cfg["family"] == "ssm":
        d_in, heads, _, n = _mamba_dims(cfg)
        return (BF16 * (d + 4 * d_in + 8 * n + heads + d_in)
                + F32 * 2 * heads)
    return BF16 * 2 * d


def decode_weight_bytes(cfg: dict) -> int:
    """Every weight a decode step reads once: the layers, the final norm
    and the LM head over the vocabulary; the embedding table is read only
    at the rows looked up (``embed_row_bytes``)."""
    per_layer = BF16 * layer_matmul_params(cfg) + layer_other_bytes(cfg)
    return (cfg["num_layers"] * per_layer + BF16 * cfg["d_model"]
            + BF16 * cfg["d_model"] * cfg["vocab_size"])


def embed_row_bytes(cfg: dict) -> int:
    return BF16 * cfg["d_model"]


def state_bytes(cfg: dict, context: int) -> int:
    """Bytes of one sequence's cached state that a decode step needs at
    this context: keys and values of the valid slots only (at most the
    window), read; or the SSM and convolution state, read and written."""
    if cfg["family"] == "ssm":
        d_in, heads, p, n = _mamba_dims(cfg)
        per_layer = F32 * heads * p * n + BF16 * 3 * (d_in + 2 * n)
        return 2 * cfg["num_layers"] * per_layer
    slots = min(context, cfg["attn_window"] or context)
    return (cfg["num_layers"] * 2 * cfg["num_kv_heads"] * cfg["head_dim"]
            * BF16 * slots)


def _slots_sum(first: int, last: int, window) -> int:
    """Sum of min(c, window) for contexts c = first..last."""
    if last < first:
        return 0
    if not window:
        return (first + last) * (last - first + 1) // 2
    below = min(last, window - 1)
    total = (first + below) * (below - first + 1) // 2 if below >= first \
        else 0
    return total + window * (last - max(first, window) + 1) \
        if last >= window else total


def tokens_flops(cfg: dict, first_ctx: int, last_ctx: int) -> int:
    """Forward operations of the tokens at contexts first..last (each
    context counts the token itself), without the LM head."""
    n = last_ctx - first_ctx + 1
    if n <= 0:
        return 0
    flops = 2 * layer_matmul_params(cfg) * n
    if cfg["family"] == "ssm":
        _, heads, p, d_state = _mamba_dims(cfg)
        flops += 4 * heads * p * d_state * n     # state update, read-out
    else:
        flops += (4 * cfg["num_heads"] * cfg["head_dim"]
                  * _slots_sum(first_ctx, last_ctx, cfg["attn_window"]))
    return cfg["num_layers"] * flops


def head_flops(cfg: dict) -> int:
    return 2 * cfg["d_model"] * cfg["vocab_size"]


def request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> int:
    """Operations one request needs: its own prompt (no padding), one LM
    head for its first token, then one step per further token."""
    return (tokens_flops(cfg, 1, prompt_len + new_tokens - 1)
            + new_tokens * head_flops(cfg))


def decode_step_bytes(cfg: dict, batch: Sequence[Tuple[int, int]],
                      steps: int) -> Iterator[int]:
    """Bytes each of a batch's ``steps`` lockstep decode steps needs.

    ``batch`` holds (prompt_len, new_tokens) per request. Step k feeds
    each request its generated token k+1; a request needs it only while
    it has tokens left (k <= new_tokens - 2). A step that no request
    needs needs no bytes.
    """
    for k in range(steps):
        active = [(n, m) for n, m in batch if k <= m - 2]
        if not active:
            yield 0
            continue
        yield (decode_weight_bytes(cfg)
               + sum(embed_row_bytes(cfg) + state_bytes(cfg, n + k + 1)
                     for n, _ in active))


# --------------------------------------------------------------------------
# Graph aggregation
# --------------------------------------------------------------------------

def gcn_block_bytes(cfg: dict, block_edges: int, distinct_src: int,
                    distinct_dst: int) -> int:
    """Bytes one block of edge visits needs: each distinct source row read
    once, the source and destination ids read, and each destination row
    written once."""
    row = cfg["features"] * F32
    return distinct_src * row + 2 * 4 * block_edges + distinct_dst * row
