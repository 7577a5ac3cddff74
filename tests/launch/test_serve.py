"""Serving driver (launch/serve.py) — smoke + tenant-isolation regression.

The serve driver now replays its batched-decode KV access stream through
``MemoryController.simulate`` in open-loop mode (ARCHITECTURE §9), so a
serve run reports modeled memory sojourns per tenant. These tests pin:

* the smoke path populates the modeled stats (finite, ordered
  percentiles, one per-tenant record per issuing tenant);
* the isolation property the serving stack exists for — with a
  bandwidth-hog tenant sharing the controller, weighted arbitration
  protects the SLO tenant's p99 where round_robin does not.

Model forward passes are real (smoke-sized) jitted JAX; keep sizes tiny.
"""

import re

import numpy as np
import pytest

from repro.core.config import MemoryControllerConfig
from repro.launch.serve import Request, Server


def _requests(rng, *, n_victim=4, n_hog=8, victim_prompt=8, hog_prompt=48,
              hog_new=24):
    """Victim tenant 0: short sparse prompts. Hog tenant 1: long prompts
    + deep decode arriving in a burst — the KV stream it induces floods
    the shared controller."""
    reqs = []
    for i in range(n_victim):
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, 250, victim_prompt)
            .astype(np.int32),
            max_new_tokens=4, arrival_cycle=i * 40, tenant=0))
    for j in range(n_hog):
        reqs.append(Request(
            rid=100 + j, prompt=rng.integers(0, 250, hog_prompt)
            .astype(np.int32),
            max_new_tokens=hog_new, arrival_cycle=j, tenant=1))
    return reqs


def _serve(arb_policy, weights, reqs):
    server = Server("h2o-danube-1.8b", smoke=True,
                    mem=MemoryControllerConfig(num_pes=2),
                    arb_policy=arb_policy, arb_weights=weights,
                    decode_interval_cycles=16)
    return server.serve([Request(**r.__dict__) for r in reqs])


def test_serve_smoke_reports_modeled_memory():
    rng = np.random.default_rng(0)
    stats = _serve("round_robin", None, _requests(rng))
    assert stats.requests == 12 and stats.batches >= 1
    assert stats.decode_steps > 0
    assert 0 < stats.modeled_p50_cycles <= stats.modeled_p95_cycles \
        <= stats.modeled_p99_cycles
    assert stats.modeled_makespan_cycles >= stats.modeled_p99_cycles
    assert set(stats.modeled_per_tenant) == {0, 1}
    for t, rec in stats.modeled_per_tenant.items():
        assert rec["n"] > 0
        assert rec["p50_sojourn"] <= rec["p99_sojourn"]
    # hog emits far more KV traffic than the victim
    assert stats.modeled_per_tenant[1]["n"] > \
        stats.modeled_per_tenant[0]["n"] * 3


def test_weighted_arbitration_protects_victim_tenant():
    """Tenant-isolation regression: same request set, same model, only
    the arbiter differs. Weighted (favoring the SLO tenant) must give
    the victim a strictly better modeled p99 than round_robin, which
    splits grants evenly with the hog's flood."""
    rng = np.random.default_rng(1)
    reqs = _requests(rng)
    rr = _serve("round_robin", None, reqs)
    wt = _serve("weighted", [8, 1], reqs)
    v_rr = rr.modeled_per_tenant[0]["p99_sojourn"]
    v_wt = wt.modeled_per_tenant[0]["p99_sojourn"]
    assert v_wt < v_rr, (v_wt, v_rr)
    # the victim's traffic is identical either way — only service changed
    assert rr.modeled_per_tenant[0]["n"] == wt.modeled_per_tenant[0]["n"]


def test_serve_outputs_and_admission_unchanged():
    """The memory model rides alongside the functional path — outputs
    and batch formation must be identical with it active."""
    rng = np.random.default_rng(2)
    reqs = _requests(rng, n_victim=2, n_hog=2, hog_new=4)
    stats = _serve("round_robin", None, reqs)
    assert stats.requests == 4
    # serve() filled outputs on its own copies; rerun on shared objects
    server = Server("h2o-danube-1.8b", smoke=True,
                    mem=MemoryControllerConfig(num_pes=2))
    server.serve(reqs)
    for r in reqs:
        assert r.output is not None and len(r.output) == r.max_new_tokens


def test_donated_decode_serves_the_same_outputs():
    """``Server._decode`` donates its cache, so each step updates it in
    place: the served tokens are those of an undonated decode, and a
    cache passed in is gone afterwards."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    reqs = _requests(rng, n_victim=2, n_hog=2, hog_new=12)
    server = Server("h2o-danube-1.8b", smoke=True,
                    mem=MemoryControllerConfig(num_pes=2))
    _, cache, cur = server._prefill(
        server.params, {"tokens": jnp.zeros((2, 8), jnp.int32)}, 16)
    server._decode(server.params, jnp.zeros((2,), jnp.int32), cache, cur)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(cache))

    server.serve(reqs)
    want = [Request(**{**r.__dict__, "output": None}) for r in reqs]
    server._decode = jax.jit(server.lm.decode_step)
    server.serve(want)
    for r, w in zip(reqs, want):
        assert len(r.output) == r.max_new_tokens
        assert r.output == w.output, r.rid


def test_serve_counters_and_token_times():
    """Host counters of one ``serve`` call against hand counts: rids 0
    and 1 (prompts 5 and 7, 3 and 2 new tokens) form one batch, rid 2
    (prompt 4, 4 new tokens) arrives after the timeout and forms
    another."""
    server = Server("h2o-danube-1.8b", smoke=True)
    reqs = [Request(0, np.arange(5, dtype=np.int32), 3, 0),
            Request(1, np.arange(7, dtype=np.int32) + 3, 2, 1),
            Request(2, np.arange(4, dtype=np.int32) + 9, 4, 200)]
    stats = server.serve(reqs)
    assert stats.batches == 2 and stats.requests == 3
    assert stats.prompt_tokens == 5 + 7 + 4
    assert stats.prefill_tokens == 2 * 7 + 1 * 4      # padded
    assert stats.decode_steps == 3 + 4
    assert stats.token_reads == 3 + 4       # one transfer a step
    assert stats.decode_slots == 2 * 3 + 1 * 4
    assert stats.useful_tokens == 3 + 2 + 4
    assert [b.batch for b in stats.batch_times] == [0, 1]
    for name in ("read_s", "dispatch_s"):
        assert getattr(stats, name) == pytest.approx(
            sum(getattr(b, name) for b in stats.batch_times))
        assert getattr(stats, name) > 0
    assert stats.model_memory_s > 0
    for b in stats.batch_times:
        assert b.prefill_s > 0 and b.decode_s > 0
        assert b.read_s + b.dispatch_s <= b.prefill_s + b.decode_s
    for r in reqs:
        assert len(r.token_times) == len(r.output) == r.max_new_tokens
        assert all(np.diff(r.token_times) > 0)
    # rows of one batch reach the host in the same step, in row order
    assert reqs[0].token_times[0] < reqs[1].token_times[0] \
        < reqs[0].token_times[1]
    # the batch counter runs on across calls
    server.serve([Request(**{**reqs[2].__dict__, "output": None})])
    assert server._batches == 3


def _uneven_requests():
    """Two batches whose rows finish at different steps: rids 0 and 1
    (3 and 2 new tokens), then rids 2 and 3 (4 and 1) after the
    timeout."""
    return [Request(0, np.arange(5, dtype=np.int32), 3, 0),
            Request(1, np.arange(7, dtype=np.int32) + 3, 2, 1),
            Request(2, np.arange(4, dtype=np.int32) + 9, 4, 200),
            Request(3, np.arange(6, dtype=np.int32) + 1, 1, 201)]


def _step_by_step(server, batch):
    """Greedy tokens of one batch from a plain loop: prefill, then in
    turn the argmax, its read to the host and the next decode step."""
    import jax.numpy as jnp
    S = max(len(r.prompt) for r in batch)
    steps = max(r.max_new_tokens for r in batch)
    prompts = np.stack([np.pad(r.prompt, (S - len(r.prompt), 0))
                        for r in batch])
    logits, cache, cur = server._prefill(
        server.params, {"tokens": jnp.asarray(prompts)}, S + steps + 8)
    outs = [[] for _ in batch]
    for step in range(steps):
        tok = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        for o, r, t in zip(outs, batch, tok):
            if step < r.max_new_tokens:
                o.append(int(t))
        logits, cache = server._decode(server.params, jnp.asarray(tok),
                                       cache, cur)
        cur = cur + 1
    return outs


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "mamba2-2.7b"])
def test_decode_loop_serves_the_step_by_step_tokens(arch):
    """Keeping one decode step in flight changes no token: each request's
    output is that of a plain step-by-step loop, one time per token,
    rising."""
    server = Server(arch, smoke=True)
    reqs = _uneven_requests()
    batches = server.admit(reqs)
    assert [[r.rid for r in b] for b in batches] == [[0, 1], [2, 3]]
    want = {r.rid: o for b in batches
            for r, o in zip(b, _step_by_step(server, b))}
    stats = server.serve(reqs)
    assert stats.token_reads == stats.decode_steps == 3 + 4
    for r in reqs:
        assert r.output == want[r.rid], r.rid
        assert len(r.token_times) == len(r.output) == r.max_new_tokens
        assert all(np.diff(r.token_times) > 0)


def test_decode_is_dispatched_before_its_tokens_are_read():
    """Step ``k`` dispatches the decode that consumes its tokens before
    they reach the host, for every row and step, and reads the batch's
    tokens in one transfer a step."""
    import time
    server = Server("h2o-danube-1.8b", smoke=True)
    reqs = _uneven_requests()
    server.serve([Request(**r.__dict__) for r in reqs])     # compiles
    decode = server._decode
    calls = []

    def timed(*args):
        calls.append(time.perf_counter())
        return decode(*args)

    server._decode = timed
    stats = server.serve(reqs)
    assert stats.token_reads == stats.decode_steps == len(calls) == 3 + 4
    first = 0
    for batch in server.admit(reqs):
        for r in batch:
            for k, t in enumerate(r.token_times):
                assert calls[first + k] < t, (r.rid, k)
        first += max(r.max_new_tokens for r in batch)


def test_decode_step_carries_the_controller_scopes():
    """The compiled decode step names its memory-layer operations: the
    scope paths a device trace files their time under."""
    import jax
    import jax.numpy as jnp
    server = Server("h2o-danube-1.8b", smoke=True)
    tokens = jnp.zeros((2, 8), jnp.int32)
    _, cache, cur = server._prefill(server.params, {"tokens": tokens}, 16)
    hlo = server._decode.lower(server.params, jnp.zeros((2,), jnp.int32),
                               cache, cur).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("mc_embed", "mc_kv_append"):
        assert any(p.startswith("jit(decode_step)/decode_step/")
                   and f"/{scope}/" in p for p in paths), scope
    assert jax.jit(server.lm.decode_step).__name__ == "decode_step"


def test_main_prints_the_counters(monkeypatch, capsys):
    """``python -m repro.launch.serve`` shows the operator the counters:
    the prompt share of the padded prefill, TTFT and TPOT, decode slot
    use, the host phases and the slowest batch's."""
    import repro.launch.serve as serve_mod
    monkeypatch.setattr(serve_mod, "use_compile_cache", lambda: "")
    monkeypatch.setattr("sys.argv", [
        "serve", "--arch", "h2o-danube-1.8b", "--smoke", "--requests", "3",
        "--prompt-len", "5", "--new-tokens", "3"])
    serve_mod.main()
    out = capsys.readouterr().out
    # one batch of 3 unpadded prompts, every row stepping all 3 times
    assert "15 prefill tokens (100.0% prompt, the rest padding)" in out
    assert re.search(r"TTFT p50=[\d.]+ms p95=[\d.]+ms", out)
    assert re.search(r"TPOT p50=[\d.]+ms p95=[\d.]+ms", out)
    assert "decode slot use 100.0% (9/9) in 3 token transfers" in out
    assert re.search(r"slowest batch 0: prefill to first token [\d.]+s, "
                     r"decode loop [\d.]+s \(token reads [\d.]+s, "
                     r"dispatch [\d.]+s\)", out)
