"""Serving: a closed loop of batches through ``Server.serve``.

Set-up builds ``Server(arch)`` (its weights come from ``key(0)``), draws
the traffic from the seed, and warms every program the traffic will use:
the prefill of each (bucket, answer length) the pool holds, the decode
step and the host-side steps of one whole ``serve`` call. The window
hands ``Server.serve`` one batch at a time, the next when the last
returns, until ``--seconds`` have passed; the batch in flight then
finishes and the window closes when it returns.

End-to-end metric: ``serve_tok_s`` counts each request's own new tokens
(not the batch's lockstep steps) over the whole window. A request's
latency is its batch's, so a tail over a window's 15-16 batches is the
slowest batch, and no tail is reported.

``correct``: after the window, every request of one served batch drawn
from the seed, and the request with the most new tokens, are compared
with the plain float32 reference: each request's prompt as the program
saw it (left-padded to its batch) followed by the tokens it was served.
``logit_gap`` is the widest gap by which a served token's reference
logit lies below the reference's best. ``logit_err`` is the largest
absolute difference between the program's logits at those tokens (the
batches fed again through the window's own compiled prefill and decode,
before the server is freed) and the reference's. Each must stay under
the cell's limit.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import List, Tuple

import numpy as np

SIZE_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
             "head_dim", "d_ff", "vocab_size", "padded_vocab", "attn_window")


@dataclasses.dataclass
class Served:
    """One ``serve`` call of the window."""

    prompts: List[np.ndarray]
    new_tokens: List[int]
    outputs: List[List[int]]
    t_start: float
    t_end: float

    @property
    def width(self) -> int:
        """The padded prompt length the program ran (the batch's longest)."""
        return max(len(p) for p in self.prompts)


@dataclasses.dataclass
class Record:
    t_start: float
    t_end: float
    served: List[Served]
    cfg: dict           # the sizes served: the configuration file's


@dataclasses.dataclass
class State:
    server: object
    pool: list
    sizes: dict


def _check_sizes(run, cfg) -> dict:
    """The program's sizes; at full size they must be the configuration's."""
    sizes = {k: getattr(cfg, k, None) for k in SIZE_KEYS}
    sizes["padded_vocab"] = cfg.padded_vocab
    if cfg.ssm is not None:
        sizes.update(ssm_d_state=cfg.ssm.d_state, ssm_expand=cfg.ssm.expand,
                     ssm_head_dim=cfg.ssm.head_dim, ssm_chunk=cfg.ssm.chunk)
    if not run.smoke:
        for k, v in run.cell.config.items():
            if k in sizes and sizes[k] != v and sizes[k] is not None:
                raise ValueError(f"{run.cell.config['name']}: the program "
                                 f"runs {k}={sizes[k]}, the file says {v}")
    return sizes


def _requests(batch):
    from repro.launch.serve import Request
    return [Request(rid=i, prompt=p, max_new_tokens=m)
            for i, (p, m) in enumerate(batch)]


def setup(run) -> State:
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import Server

    server = Server(run.cell.config["arch"], smoke=run.smoke)
    sizes = _check_sizes(run, server.cfg)
    pool = run.cell.generator().make(run.cell.traffic, server.cfg.vocab_size,
                                     run.seed)
    def shape(batch):
        return max(len(p) for p, _ in batch), max(m for _, m in batch)

    for width, new in sorted({shape(b) for b in pool}):
        out = server._prefill(
            server.params,
            {"tokens": jnp.zeros((len(pool[0]), width), jnp.int32)},
            width + new + 8)
        jax.block_until_ready(out)
    server.serve(_requests(min(pool, key=shape)))
    if run.trace:
        # host spans around the program's own steps, so that the trace
        # can say what the host was doing in each idle gap
        for attr in ("_prefill", "_decode", "model_memory"):
            setattr(server, attr, _spanned(f"Server.{attr.lstrip('_')}",
                                           getattr(server, attr)))
    return State(server=server, pool=pool, sizes=sizes)


def _spanned(name: str, fn):
    import jax

    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    return call


def window(run, state: State, stop) -> Record:
    import jax
    served = []
    t0 = time.time()
    i = 0
    while True:
        batch = state.pool[i % len(state.pool)]
        reqs = _requests(batch)
        with jax.profiler.TraceAnnotation("Server.serve"):
            ts = time.time()
            state.server.serve(reqs)
            te = time.time()
        served.append(Served([r.prompt for r in reqs],
                             [r.max_new_tokens for r in reqs],
                             [list(r.output) for r in reqs], ts, te))
        i += 1
        if stop(te - t0, i):
            return Record(t0, te, served, reference_config(run, state))


def end_to_end(run, rec: Record) -> dict:
    tokens = sum(sum(s.new_tokens) for s in rec.served)
    return {"serve_tok_s": (tokens / (rec.t_end - rec.t_start), "tokens/s")}


def counts(rec: Record) -> Tuple[int, int]:
    attempted = sum(len(s.outputs) for s in rec.served)
    failed = sum(len(o) != m for s in rec.served
                 for o, m in zip(s.outputs, s.new_tokens))
    return attempted, failed


def release(state: State) -> None:
    state.server.params = None
    state.server = None
    gc.collect()


def sample(run, rec: Record) -> List[Tuple[Served, int]]:
    """(served batch, row) of every request of one served batch drawn from
    the seed, so that every slot of a batch is compared, and of the
    request with the most new tokens (then the longest prompt)."""
    rng = np.random.default_rng([run.seed, 0x5E7E])
    s = rec.served[int(rng.integers(len(rec.served)))]
    picks = [(s, i) for i in range(len(s.prompts))]
    longest = max(((t, i) for t in rec.served for i in range(len(t.prompts))),
                  key=lambda ti: (len(ti[0].outputs[ti[1]]), ti[0].width))
    if longest[0] is not s:
        picks.append(longest)
    return picks


def replay(server, s: Served) -> np.ndarray:
    """The program's logits (batch, new tokens, vocab) at every token it
    served in one batch: the batch's prompts and served tokens fed again
    through the window's own compiled prefill and decode programs, as
    ``Server.run_batch`` feeds them."""
    import jax.numpy as jnp
    prompts = np.stack([np.pad(p, (s.width - len(p), 0)) for p in s.prompts])
    steps = max(s.new_tokens)
    logits, cache, cur = server._prefill(
        server.params, {"tokens": jnp.asarray(prompts)}, s.width + steps + 8)
    out = [np.asarray(logits, np.float32)]
    for k in range(steps - 1):
        tok = np.asarray([o[k] if k < len(o) else 0 for o in s.outputs],
                         np.int32)
        logits, cache = server._decode(server.params, jnp.asarray(tok),
                                       cache, cur)
        cur = cur + 1
        out.append(np.asarray(logits, np.float32))
    return np.stack(out, 1)


def program_logits(server, picks) -> List[np.ndarray]:
    """(served tokens, vocab) logits of each picked request, replayed."""
    batches = {}
    out = []
    for s, i in picks:
        if id(s) not in batches:
            batches[id(s)] = replay(server, s)
        out.append(batches[id(s)][i, :len(s.outputs[i])])
    return out


def reference_logits(ref, weights, cfg: dict, picks, rows: int,
                     precision: str = "float32") -> List[np.ndarray]:
    """(served tokens, vocab) logits of the plain reference for each picked
    request: its prompt as the program saw it (left-padded to its batch)
    followed by its served tokens. The reference runs ``rows`` sequences
    at a time, so that it fits beside its weights."""
    reqs = [(np.pad(s.prompts[i], (s.width - len(s.prompts[i]), 0)),
             s.outputs[i]) for s, i in picks]
    P = max(len(o) for _, o in reqs)
    out = []
    for first in range(0, len(reqs), rows):
        block = reqs[first:first + rows]
        # few shapes, so that the reference's programs come from the cache
        L = -(-max(len(p) + len(o) for p, o in block) // 512) * 512
        tokens = np.zeros((rows, L), np.int32)
        pos = np.zeros((rows, P), np.int32)
        for i, (p, o) in enumerate(block):
            seq = np.concatenate([p, np.asarray(o, np.int32)])
            tokens[i, :len(seq)] = seq
            pos[i, :len(o)] = len(p) - 1 + np.arange(len(o))
        lg = ref.logits(weights, cfg, tokens, pos, precision)
        out += [lg[i, :len(o)] for i, (_, o) in enumerate(block)]
    return out


def compare(want: List[np.ndarray], tokens: List[np.ndarray],
            got: List[np.ndarray]) -> dict:
    """``logit_gap``: the widest gap by which a token's reference logit
    lies below the reference's best; ``logit_err``: the largest absolute
    difference between the logits compared and the reference's."""
    gap = max(float(np.max(w.max(-1) - np.take_along_axis(
        w, np.asarray(t, np.int64)[:, None], -1)[:, 0]))
        for w, t in zip(want, tokens))
    err = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
    return {"logit_gap": gap, "logit_err": err}


def reference_config(run, state: State) -> dict:
    """The configuration the reference runs: the file's, or at smoke size
    the program's own sizes."""
    if not run.smoke:
        return run.cell.config
    return {**run.cell.config, **state.sizes}


def values(ref, w, cfg: dict, picks, rows: int, want, got=None,
           control=None) -> dict:
    """The numbers compared for the picked requests: the program's served
    tokens and replayed logits ``got``, or, with ``control`` set, the
    reference in that precision put in the program's place: its logits,
    and at each position the token it puts first."""
    if control:
        got = reference_logits(ref, w, cfg, picks, rows, control)
        tokens = [g.argmax(-1) for g in got]
    else:
        tokens = [s.outputs[i] for s, i in picks]
    return compare(want, tokens, got)


def check(run, state: State, rec: Record):
    """The program's served tokens and replayed logits (or the control's)
    against the plain float32 reference, which runs once the server is
    freed."""
    from bench.harness import Check
    picks = sample(run, rec)
    got = None if run.control else program_logits(state.server, picks)
    release(state)
    ref = run.cell.reference()
    w = ref.weights(rec.cfg)
    rows = int(run.cell.spec["reference_rows"])
    want = reference_logits(ref, w, rec.cfg, picks, rows)
    found = values(ref, w, rec.cfg, picks, rows, want, got, run.control)
    del w
    gc.collect()
    limits = run.cell.limits()
    return [Check(k, v, float(limits[k])) for k, v in found.items()]


def readings(cell, seeds, units: int, precision: str, smoke: bool = False):
    """The program's and the control's readings on each seed, in one
    process: one server, a window of ``units`` batches per seed, then the
    reference once the server is freed."""
    from bench.harness import Run
    state = None
    taken = []
    for seed in seeds:
        run = Run(cell=cell, seed=seed, seconds=0.0, trace=False,
                  t_start=time.time(), smoke=smoke)
        if state is None:
            state = setup(run)
        state.pool = cell.generator().make(
            cell.traffic, state.server.cfg.vocab_size, seed)
        rec = window(run, state, lambda elapsed, done: done >= units)
        picks = sample(run, rec)
        taken.append((seed, rec, picks, program_logits(state.server, picks)))
    release(state)
    ref = cell.reference()
    rows = int(cell.spec["reference_rows"])
    w = ref.weights(taken[0][1].cfg)
    out = []
    for seed, rec, picks, got in taken:
        want = reference_logits(ref, w, rec.cfg, picks, rows)
        out.append({"seed": seed,
                    "program": values(ref, w, rec.cfg, picks, rows, want,
                                      got),
                    "control": values(ref, w, rec.cfg, picks, rows, want,
                                      control=precision)})
    return out
