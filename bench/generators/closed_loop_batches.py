"""Batches of requests for a closed loop, from a traffic file.

Every seed gets the same work: the batches' sizes, and their order, are
drawn once from the traffic's own ``shape_seed``: prompt lengths
lognormal (``median``, ``sigma``), clipped to [``min``, ``max``] and
rounded up to the next bucket; each batch holds ``per_batch[i]`` answers
of ``tokens[i]`` new tokens. So a window that ends part-way through the
pool has run the same batches whatever the seed. ``--seed`` decides
which request of a batch gets which answer length and draws the token
ids (Zipf over the vocabulary, as ``repro.data.synthetic.zipf_tokens``
draws them).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

#: one request: (prompt token ids, new tokens)
Request = Tuple[np.ndarray, int]


def prompt_lengths(traffic: dict) -> np.ndarray:
    """(pool_batches, batch) prompt lengths, the same for every seed."""
    p = traffic["prompt"]
    rng = np.random.default_rng(traffic["shape_seed"])
    shape = (traffic["pool_batches"], traffic["batch"])
    raw = p["median"] * np.exp(p["sigma"] * rng.standard_normal(shape))
    raw = np.clip(np.ceil(raw), p["min"], p["max"])
    buckets = np.asarray(sorted(p["buckets"]))
    return buckets[np.searchsorted(buckets, raw)]


def answer_lengths(traffic: dict) -> np.ndarray:
    a = traffic["answers"]
    out = np.repeat(a["tokens"], a["per_batch"])
    if len(out) != traffic["batch"]:
        raise ValueError("answers.per_batch must add up to the batch size")
    return out


def zipf_tokens(rng: np.random.Generator, n: int, vocab: int,
                alpha: float) -> np.ndarray:
    z = rng.zipf(alpha, size=n).astype(np.int64)
    return ((z - 1) % vocab).astype(np.int32)


def make(traffic: dict, vocab: int, seed: int) -> List[List[Request]]:
    lens = prompt_lengths(traffic)
    answers = answer_lengths(traffic)
    rng = np.random.default_rng(seed)
    batches = []
    for b in range(len(lens)):
        order = rng.permutation(traffic["batch"])
        batches.append([
            (zipf_tokens(rng, int(lens[b][i]), vocab, traffic["token_zipf"]),
             int(answers[j]))
            for i, j in zip(range(traffic["batch"]), order)])
    return batches
