#!/usr/bin/env python3
"""Proof that the system runs on a TPU, in one process.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: sharded training only

One chip, at h2o-danube-1.8b's published widths (random weights):

1. device  — fails unless JAX's first device is a TPU; no CPU fallback.
2. serve   — ``Server.serve`` answers 8 seeded requests (prompts of
   128-512 tokens, 16 new tokens each). Tokens are in the vocabulary,
   logits are finite, cached decode agrees with the model's full forward
   over prompt plus answer, and the same weights with the controller's
   scheduler off give the same greedy tokens (ARCHITECTURE §1).
3. controller — ``MemoryController(use_pallas=True)`` gathers, scatters
   (set and add) and bulk-reads and -writes the 32000 x 2560 bf16
   embedding table with 4096 seeded Zipf ids; every result equals its
   ``jnp`` reference exactly, and every compiled program holds a Pallas
   kernel (``tpu_custom_call``), not the interpreter.
4. simulator — two pinned golden cases of ``MemoryController.simulate``
   (host numpy) recomputed here equal their committed ``tests/goldens``
   records, and the cache engine's ``lax.scan`` serves the GCN trace on
   the device hit for hit like the LRU oracle.

``--four-chips`` runs only ``Trainer`` on a 2x2 (data, model) mesh for 8
steps of batch 8 x 512 tokens, with parameters and Adam state created
in their shards, and checks it against a one-chip forward loss.

The last line of standard output is the JSON result; it is printed only
when every phase passed. Times printed on earlier lines are set-up
figures of a bring-up run, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "h2o-danube-1.8b"
GOLDEN_CASES = ("paper_combined_gcn", "serving_hog_victim_weighted")


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------------
# phase 2: serving
# --------------------------------------------------------------------------

def _requests(seed: int, vocab: int, n: int, prompt_lens, new_tokens: int):
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n)
    return [Request(rid=i, max_new_tokens=new_tokens,
                    prompt=rng.integers(0, vocab, int(s)).astype(np.int32))
            for i, s in enumerate(lens)]


def _greedy(lm, params, prompts, new_tokens: int, max_len: int,
            prefill=None, decode=None):
    """Greedy cached decode; returns (tokens (B, T), logits (T, B, V))."""
    import jax
    import jax.numpy as jnp
    prefill = prefill or jax.jit(
        lambda p, b, ml: lm.prefill(p, b, max_len=ml), static_argnums=(2,))
    decode = decode or jax.jit(lm.decode_step)
    logits, cache, cur = prefill(params, {"tokens": jnp.asarray(prompts)},
                                 max_len)
    toks, steps = [], []
    for _ in range(new_tokens):
        steps.append(logits)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(tok)
        logits, cache = decode(params, tok, cache, cur)
        cur = cur + 1
    return np.stack([np.asarray(t) for t in toks], 1), jnp.stack(steps)


def phase_serve(seed: int, *, smoke: bool = False, n_requests: int = 8,
                prompt_lens=(128, 512), new_tokens: int = 16) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import Server
    from repro.models.lm import build_lm

    t0 = time.perf_counter()
    server = Server(ARCH, smoke=smoke)
    jax.block_until_ready(server.params)
    cfg, lm, params = server.cfg, server.lm, server.params
    setup_s = time.perf_counter() - t0

    def serve_once():
        reqs = _requests(seed, cfg.vocab_size, n_requests, prompt_lens,
                         new_tokens)
        t = time.perf_counter()
        stats = server.serve(reqs)
        return reqs, stats, time.perf_counter() - t

    reqs, stats, first_s = serve_once()          # compiles prefill/decode
    reqs2, _, serve_s = serve_once()             # same shapes: no compile
    log(f"serve: {ARCH} d_model={cfg.d_model} layers={cfg.num_layers} "
        f"vocab={cfg.vocab_size}; {stats.requests} requests in "
        f"{stats.batches} batch(es), {stats.prefill_tokens} prefill tokens")
    log(f"serve: set-up {setup_s:.3f}s, compile + first serve "
        f"{first_s:.3f}s, second serve {serve_s:.3f}s (bring-up timings, "
        "not metrics)")

    gen = np.array([r.output for r in reqs], np.int64)
    check(gen.shape == (n_requests, new_tokens), f"output shape {gen.shape}")
    check(np.array_equal(gen, np.array([r.output for r in reqs2])),
          "two serves of the same requests gave different tokens")
    check(bool(((gen >= 0) & (gen < cfg.vocab_size)).all()),
          "token outside [0, vocab_size)")

    # The server's batch, as it left-padded it.
    S = max(len(r.prompt) for r in reqs)
    prompts = np.stack([np.pad(r.prompt, (S - len(r.prompt), 0))
                        for r in reqs])
    max_len = S + new_tokens + 8

    # Cached decode through the server's own compiled steps.
    toks, dec = _greedy(lm, params, prompts, new_tokens, max_len,
                        server._prefill, server._decode)
    check(np.array_equal(toks, gen),
          "replaying the server's prefill/decode gave other tokens")
    dec = np.asarray(dec.astype(jnp.float32))                 # (T, B, V)
    check(bool(np.isfinite(dec).all()), "non-finite decode logits")

    # Reference: the full forward over prompt + answer, no cache.
    full = jnp.asarray(np.concatenate([prompts, gen], 1), jnp.int32)
    ref_logits = jax.jit(lm.forward)(params, {"tokens": full})[0]
    ref = np.asarray(ref_logits[:, S - 1:S - 1 + new_tokens,
                                :cfg.vocab_size].astype(jnp.float32))
    ref = ref.transpose(1, 0, 2)                              # (T, B, V)
    check(bool(np.isfinite(ref).all()), "non-finite forward logits")
    scale = float(np.abs(ref).max())
    err = float(np.abs(dec - ref).max())
    # bf16 keeps 8 significant bits; 24 layers of bf16 residual stream
    # differ by a few of its ulps between the cached and uncached paths.
    tol = 0.05 * max(scale, 1.0)
    check(err <= tol, f"decode vs forward logits: max |diff| {err:.4g} "
                      f"> {tol:.4g} (max |logit| {scale:.4g})")
    # Greedy agreement. A token may differ only where the forward's own
    # top two are within the logit tolerance (a bf16 near-tie).
    ref_top = ref.argmax(-1).T                                # (B, T)
    picked = np.take_along_axis(ref, gen.T[..., None], -1)[..., 0].T
    gap = ref.max(-1).T - picked
    mism = ref_top != gen
    check(bool((gap[mism] <= tol).all()),
          f"greedy token differs from the forward's by a logit gap of "
          f"{gap[mism].max() if mism.any() else 0:.4g} > {tol:.4g}")
    log(f"serve: decode vs forward max |logit diff| {err:.6g} "
        f"(tolerance {tol:.4g}); greedy tokens equal at "
        f"{int((~mism).sum())}/{mism.size} positions, the rest bf16 "
        f"near-ties (gap <= {float(gap[mism].max()) if mism.any() else 0:.4g})")

    # Engine toggle: scheduler off, same weights, same greedy tokens.
    mc_off = dataclasses.replace(cfg.mc, scheduler=dataclasses.replace(
        cfg.mc.scheduler, enabled=False))
    lm_off = build_lm(dataclasses.replace(cfg, mc=mc_off))
    toks_off, _ = _greedy(lm_off, params, prompts, new_tokens, max_len)
    check(np.array_equal(toks_off, gen),
          "scheduler off changed the greedy tokens")
    log("serve: scheduler-off greedy tokens identical")
    return {"setup_s": setup_s, "first_serve_s": first_s,
            "serve_s": serve_s, "logit_err": err}


# --------------------------------------------------------------------------
# phase 3: controller engines (Pallas kernels) on HBM
# --------------------------------------------------------------------------

def phase_controller(seed: int, *, rows: int = 32000, d: int = 2560,
                     n: int = 4096) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core.config import MemoryControllerConfig
    from repro.core.controller import MemoryController
    from repro.kernels.sorted_scatter.ref import scatter_ref

    rng = np.random.default_rng(seed)
    # Zipf-ranked ids over a random row order (hot rows spread out).
    ids = rng.permutation(rows)[(rng.zipf(1.2, n) - 1) % rows]
    ids = jnp.asarray(ids, jnp.int32)
    # Small integers: every f32 sum of them is exact, so ``add`` has one
    # right answer whatever order duplicates accumulate in.
    k1, k2 = jax.random.split(jax.random.key(seed))
    table = jnp.round(4 * jax.random.normal(k1, (rows, d))).astype(
        jnp.bfloat16)
    vals = jnp.round(4 * jax.random.normal(k2, (n, d))).astype(jnp.bfloat16)
    offset = (rows - n) * d // 3                  # bulk_write destination
    mc = MemoryController(MemoryControllerConfig(), use_pallas=True)

    def add_ref(t, i, v):
        return t.astype(jnp.float32).at[i].add(
            v.astype(jnp.float32)).astype(t.dtype)

    def write_ref(dst, src):
        return jax.lax.dynamic_update_slice(
            dst.reshape(-1), src.reshape(-1), (offset,)).reshape(dst.shape)

    cases = {
        "gather": (mc.gather, (table, ids),
                   lambda: jnp.take(table, ids, axis=0)),
        "scatter_set": (lambda t, i, v: mc.scatter(t, i, v, mode="set"),
                        (table, ids, vals),
                        lambda: scatter_ref(table, ids, vals, "set")),
        "scatter_add": (lambda t, i, v: mc.scatter(t, i, v, mode="add"),
                        (table, ids, vals),
                        lambda: jax.jit(add_ref)(table, ids, vals)),
        "bulk_read": (mc.bulk_read, (table,), lambda: table),
        "bulk_write": (lambda t, v: mc.bulk_write(t, v, offset_elems=offset),
                       (table, vals), lambda: jax.jit(write_ref)(table, vals)),
    }
    log(f"controller: table {rows}x{d} bf16, {n} Zipf ids "
        f"({len(np.unique(np.asarray(ids)))} distinct rows)")
    # On the CPU (the tests) the kernels run in the Pallas interpreter,
    # which leaves no kernel in the program to look for.
    on_tpu = jax.default_backend() == "tpu"
    out = {}
    for name, (fn, args, ref) in cases.items():
        t = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t
        check(not on_tpu or "tpu_custom_call" in compiled.as_text(),
              f"{name}: no compiled Pallas kernel in the program")
        got = jax.block_until_ready(compiled(*args))
        want = ref()
        check(got.shape == want.shape and got.dtype == want.dtype,
              f"{name}: {got.shape} {got.dtype} != {want.shape} {want.dtype}")
        check(bool(jnp.array_equal(got, want)),
              f"{name}: result differs from its jnp reference")
        log(f"controller: {name} exact, Pallas kernel compiled "
            f"({compile_s:.3f}s compile)")
        out[name] = compile_s
    return out


# --------------------------------------------------------------------------
# phase 4: simulator goldens
# --------------------------------------------------------------------------

def phase_simulator(cases=GOLDEN_CASES) -> None:
    import jax.numpy as jnp
    from repro.core import cache_engine
    sys.path.insert(0, str(ROOT / "tests" / "core"))
    from golden_cases import CASES, GOLDEN_DIR, golden_record
    for name in cases:
        with open(Path(GOLDEN_DIR) / f"{name}.json") as f:
            want = json.load(f)
        t = time.perf_counter()
        got = golden_record(name)
        check(sorted(got) == sorted(want), f"{name}: record keys differ")
        for key in sorted(want):
            check(got[key] == want[key],
                  f"{name}: {key} = {got[key]!r}, golden {want[key]!r}")
        log(f"simulator: {name} equals its golden "
            f"({time.perf_counter() - t:.3f}s)")

    # The staged pipeline above is host numpy. The cache engine's
    # functional model is the device part: its one-beat-per-request
    # ``lax.scan`` serves the GCN golden trace and must match the
    # pure-python LRU oracle hit for hit.
    config, trace, _ = CASES[cases[0]]
    rows, _ = trace()
    lids = jnp.asarray(rows, jnp.int32)
    table = jnp.arange((int(rows.max()) + 1) * 8,
                       dtype=jnp.float32).reshape(-1, 8)
    t = time.perf_counter()
    _, hits, lines = cache_engine.simulate_trace_seq(
        cache_engine.init_cache(config.cache, 8), lids, table)
    want, rate = cache_engine.hit_rate_oracle_seq(config.cache, rows)
    check(np.array_equal(np.asarray(hits), want),
          "cache engine scan: hits differ from the LRU oracle")
    check(bool(jnp.array_equal(lines, table[lids])),
          "cache engine scan: served lines differ from the table")
    log(f"simulator: cache-engine scan over {rows.shape[0]} requests "
        f"matches the LRU oracle (hit rate {rate:.4f}, "
        f"{time.perf_counter() - t:.3f}s)")


# --------------------------------------------------------------------------
# --four-chips: sharded training
# --------------------------------------------------------------------------

def phase_train_sharded(seed: int, *, smoke: bool = False, steps: int = 8,
                        batch: int = 8, seq: int = 512) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.launch.train import Trainer, TrainerConfig
    from repro.models.lm import build_lm
    from repro.models.sharding import make_mesh
    from repro.optim.adamw import OptimizerConfig

    mesh = make_mesh((2, 2), ("data", "model"))
    tc = TrainerConfig(arch=ARCH, smoke=smoke, steps=steps, seed=seed,
                       batch_override=batch, seq_override=seq, log_every=1,
                       # Adam's first update moves every weight by the
                       # learning rate: the loss jumps at step 1 (to 20.3
                       # at 1e-3, 13.1 at 1e-4 on a v5e) before falling.
                       opt=OptimizerConfig(peak_lr=1e-4, warmup_steps=1))
    t0 = time.perf_counter()
    trainer = Trainer(tc, mesh)
    params, opt, _ = trainer.init_state()
    jax.block_until_ready((params, opt))
    log(f"train: {ARCH} d_model={trainer.cfg.d_model} layers="
        f"{trainer.cfg.num_layers}, mesh {dict(mesh.shape)}, batch {batch} "
        f"x {seq}; sharded init {time.perf_counter() - t0:.3f}s")

    # Every device holds about a quarter of the parameter bytes. Over the
    # quarter: the embedding table, split over `model` only (0.0112 of
    # the bytes for h2o-danube), and the replicated norm scales.
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    per_dev = {d: 0 for d in mesh.devices.flat}
    for x in jax.tree.leaves(params):
        for s in x.addressable_shards:
            per_dev[s.device] += s.data.nbytes
    shares = [b / total for b in per_dev.values()]
    check(all(0.24 <= s <= 0.30 for s in shares),
          f"parameter shares per device {shares}")
    log(f"train: parameter bytes {total}, per-device shares "
        f"{[round(s, 4) for s in shares]}")

    # One-chip reference loss of step 0, on devices()[0].
    dev0 = jax.devices()[0]
    lm1 = build_lm(trainer.cfg)
    batch0 = {k: jnp.asarray(v) for k, v in trainer.data.batch_at(0).items()}
    ref = float(jax.jit(lm1.loss)(jax.device_put(params, dev0),
                                  jax.device_put(batch0, dev0))[0])
    del params, opt

    t = time.perf_counter()
    hist = trainer.run()["history"]
    log(f"train: losses {hist} ({time.perf_counter() - t:.3f}s for "
        f"{steps} steps, compile included)")
    check(all(np.isfinite(hist)), "non-finite loss")
    check(abs(hist[0] - ref) <= 2e-3 * abs(ref),
          f"step-0 loss {hist[0]} vs one-chip forward {ref}")
    check(hist[-1] < hist[0], "loss did not fall")
    log(f"train: step-0 loss {hist[0]:.6f} vs one-chip forward {ref:.6f}")
    return {"history": hist, "ref_loss": ref, "shares": shares}


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training path on a 2x2 mesh")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py must run from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # The TPU library writes its logs under /tmp unless told otherwise.
    (ROOT / ".tpu_logs").mkdir(exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))

    import jax
    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke.py needs a TPU; JAX found none", file=sys.stderr)
        return 1
    log(f"compile cache: {use_compile_cache()}")

    if args.four_chips:
        if len(devices) < 4:
            print(f"--four-chips needs 4 devices, found {len(devices)}",
                  file=sys.stderr)
            return 1
        phase_train_sharded(args.seed)
    else:
        phase_serve(args.seed)
        phase_controller(args.seed)
        phase_simulator()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
