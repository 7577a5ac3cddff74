"""Per-architecture workload zoo: captured model traces (ARCHITECTURE §13).

One function — :func:`capture_model_trace` — runs each registry
architecture's *smoke* configuration through a fixed exercise script with
a :class:`~repro.core.capture.TraceCapture` active, and returns the
``(pe_id, row_id, rw, bytes, arrival)`` request stream the model actually
emitted. The script covers every controller-routed traffic class:

* **forward/train** — embedding gathers (``mc_embed``), MoE expert
  dispatch+combine (multi-port: expert = PE), audio/vision frontend
  streaming reads;
* **embedding-gradient update** — the irregular WRITE stream
  (``mc_scatter``, mode="add");
* **prefill + decode steps** — 1-D decode-token gathers (now routed
  through the controller), KV-page bulk-write appends
  (``mc_kv_append``), SSM state rewrites (mamba).

Capture runs execute eagerly with ``scan_layers=False`` (the supported
unrolled layer walk) so the hooks see concrete values; any residual
traced op is skipped and counted, and the zoo asserts the count is zero.

The replay contract is closed-loop: ``TraceCapture.replay_arrays`` folds
ports onto ``config.num_pes`` and drops the logical arrival clock, so
``MemoryController.simulate`` keeps its cache + batch-scheduler stages
(nonzero arrivals would flip it into open-loop serving mode).

Pinned traces (one representative per model family) live as JSON under
``tests/goldens/traces/`` — regenerable with
``scripts/regen_goldens.py --traces`` — and feed the golden harness
(``tests/core/golden_cases.py``) plus the per-family tuned-geometry case
of ``tests/core/test_paper_figures.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

from repro.configs import registry
from repro.configs.base import ShapeConfig
from repro.core.capture import TraceCapture

# Fixed zoo shape: big enough that scheduler batches, cache working sets
# and multi-port contention are non-degenerate; small enough that all 10
# architectures capture in seconds on CPU.
CAPTURE_BATCH = 4
CAPTURE_SEQ = 64
CAPTURE_DECODE_STEPS = 8
TRACE_SEED = 0
# Replay granularity: the capture is row-indexed; every row is priced at
# the goldens' canonical 4 KiB stride (per-request true transfer sizes
# stay available in ``TraceCapture.rows()['nbytes']``).
REPLAY_ROW_BYTES = 4096

# One pinned golden trace per model family (family -> registry id).
FAMILY_REPRESENTATIVE = {
    "dense": "yi_34b",
    "moe": "mixtral_8x7b",
    "ssm": "mamba2_2p7b",
    "hybrid": "jamba_v0p1_52b",
    "encoder": "hubert_xlarge",
    "vlm": "internvl2_76b",
}

TRACE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "..", "..", "..", "tests", "goldens", "traces"))


def arch_families() -> dict:
    """registry id -> family string, for all 10 architectures."""
    return {a: registry.get_arch(a, smoke=True).family
            for a in registry.ARCH_IDS}


def pinned_trace_path(arch: str) -> str:
    return os.path.join(TRACE_DIR, f"{registry.canonical(arch)}.json")


def capture_model_trace(arch: str, *, seed: int = TRACE_SEED,
                        batch: int = CAPTURE_BATCH, seq: int = CAPTURE_SEQ,
                        decode_steps: int = CAPTURE_DECODE_STEPS
                        ) -> TraceCapture:
    """Run the fixed exercise script for ``arch`` (smoke config) under an
    active recorder; returns the captured trace.

    Deterministic for fixed ``(arch, seed, batch, seq, decode_steps)``
    within a process/platform: params and data are seeded, decode feeds
    back argmax tokens. Raises if any hooked op was skipped under tracing
    (the zoo must observe *all* traffic) or if the capture is empty.
    """
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import make_batch
    from repro.models.lm import build_lm

    cfg = registry.get_arch(arch, smoke=True)
    # Eager unrolled layer walk, no remat (jax.checkpoint traces its
    # body), so capture hooks see concrete values.
    cfg = dataclasses.replace(cfg, scan_layers=False, remat=False)
    lm = build_lm(cfg)
    params = lm.init(jax.random.PRNGKey(seed))
    shape = ShapeConfig(f"capture_{seq}x{batch}", seq, batch, "train")
    data = make_batch(cfg, shape, step=0, seed=seed)

    with TraceCapture() as cap:
        lm.forward(params, data)
        if "tokens" in data:
            tokens = jnp.asarray(data["tokens"])
            table = params["embed"]["table"]
            grad_rows = jnp.ones((*tokens.shape, table.shape[-1]),
                                 table.dtype)
            lm.embedding_grad_update(params, tokens, grad_rows)
        if cfg.family != "encoder":
            serve = {k: v for k, v in data.items()
                     if k not in ("labels", "loss_mask")}
            logits, cache, cur = lm.prefill(params, serve,
                                            max_len=seq + decode_steps)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            for _ in range(decode_steps):
                logits, cache = lm.decode_step(params, tok, cache, cur)
                cur = cur + 1
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if cap.n_skipped_traced:
        raise RuntimeError(
            f"{arch}: {cap.n_skipped_traced} capture hook(s) saw traced "
            "values — the zoo must run eagerly (scan_layers=False)")
    if len(cap) == 0:
        raise RuntimeError(f"{arch}: captured trace is empty")
    return cap


@functools.lru_cache(maxsize=None)
def cached_capture(arch: str, seed: int = TRACE_SEED,
                   batch: int = CAPTURE_BATCH, seq: int = CAPTURE_SEQ,
                   decode_steps: int = CAPTURE_DECODE_STEPS) -> TraceCapture:
    """Memoized :func:`capture_model_trace` — tests and benchmarks share
    one capture per configuration. Treat the result as read-only."""
    return capture_model_trace(arch, seed=seed, batch=batch, seq=seq,
                               decode_steps=decode_steps)


def load_pinned_trace(arch: str) -> TraceCapture:
    """The checked-in golden trace for ``arch`` (family representative)."""
    return TraceCapture.load(pinned_trace_path(arch))


def write_pinned_traces(verbose: bool = True) -> list:
    """(Re)capture and write the per-family pinned traces; returns the
    written paths (``scripts/regen_goldens.py --traces``)."""
    os.makedirs(TRACE_DIR, exist_ok=True)
    paths = []
    for family, arch in sorted(FAMILY_REPRESENTATIVE.items()):
        cap = capture_model_trace(arch)
        path = pinned_trace_path(arch)
        cap.save(path)
        paths.append(path)
        if verbose:
            counts = ", ".join(f"{k}={v}" for k, v in
                               sorted(cap.op_counts().items()))
            print(f"wrote {path}  [{family}] n={len(cap)} ({counts})")
    return paths


def summarize(cap: TraceCapture) -> dict:
    """Machine-readable shape of a captured trace (benchmark payloads)."""
    r = cap.rows()
    return {
        "n_requests": int(r["row_id"].size),
        "n_ops": int(cap.n_ops),
        "n_ports": int(cap.n_ports),
        "n_rows_total": int(cap.n_rows_total),
        "write_fraction": float(r["rw"].mean()) if r["rw"].size else 0.0,
        "total_bytes": int(r["nbytes"].sum()),
        "unique_rows": int(np.unique(r["row_id"]).size),
        "op_counts": cap.op_counts(),
    }
