"""The benchmark's operation and byte counters against hand counts, and
the peak table."""

import json

import numpy as np
import pytest

from bench import counts, harness
from bench_cells import smoke_cell

DANUBE = json.loads((harness.BENCH / "configs" /
                     "h2o-danube-1.8b.json").read_text())
MAMBA = json.loads((harness.BENCH / "configs" /
                    "mamba2-2.7b.json").read_text())
# h2o-danube at its smoke widths (repro.configs.h2o_danube_1p8b)
TINY = {**DANUBE, "num_layers": 2, "d_model": 64, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab_size": 256,
        "padded_vocab": 256, "attn_window": 8}


def test_layer_params_match_hand_count_and_program():
    # wq 64x64 + wk, wv 64x32 each + wo 64x64 + gate, up, down 64x128 each
    assert counts.layer_matmul_params(TINY) == 4096 + 2 * 2048 + 4096 \
        + 3 * 8192
    from repro.configs import get_arch
    from repro.models.params import param_count_tree
    cfg = get_arch("h2o-danube-1.8b", smoke=True)
    total = param_count_tree(cfg)
    layer_norms = 2 * 64
    embed_head = 2 * 256 * 64
    assert total == 2 * (counts.layer_matmul_params(TINY) + layer_norms) \
        + embed_head + 64


def test_decode_weight_bytes_full_width():
    # 1.75 G layer weights, norms, final norm, LM head over 32000 columns
    per_layer = 2 * (2560 * 2560 + 2 * 2560 * 640 + 2560 * 2560
                     + 3 * 2560 * 6912) + 2 * 2 * 2560
    want = 24 * per_layer + 2 * 2560 + 2 * 2560 * 32000
    assert counts.decode_weight_bytes(DANUBE) == want


def test_kv_bytes_count_valid_context_only():
    slot = 24 * 2 * 8 * 80 * 2          # keys and values, every layer, bf16
    assert counts.state_bytes(DANUBE, 100) == 100 * slot
    assert counts.state_bytes(DANUBE, 5000) == 4096 * slot   # the window


def test_ssm_state_bytes_do_not_grow_with_context():
    per_layer = 4 * 80 * 64 * 128 + 2 * 3 * (5120 + 256)
    assert counts.state_bytes(MAMBA, 10) == 2 * 64 * per_layer
    assert counts.state_bytes(MAMBA, 10**6) == counts.state_bytes(MAMBA, 10)


def test_decode_step_bytes_count_requests_still_decoding():
    batch = [(5, 3), (7, 1)]             # (prompt, new tokens)
    steps = list(counts.decode_step_bytes(TINY, batch, 3))
    w, e = counts.decode_weight_bytes(TINY), counts.embed_row_bytes(TINY)
    slot = 2 * 2 * 2 * 16 * 2
    # step 0 feeds request 0 its first token (context 6); request 1 needs
    # no step; step 1 has context 7; step 2 is needed by nobody
    assert steps == [w + e + 6 * slot, w + e + 7 * slot, 0]


def test_slots_sum_caps_at_the_window():
    assert counts._slots_sum(1, 4, None) == 10
    assert counts._slots_sum(6, 10, 8) == 6 + 7 + 8 + 8 + 8
    assert counts._slots_sum(9, 11, 8) == 24
    assert counts._slots_sum(3, 2, 8) == 0


def test_request_flops_hand_count():
    cfg = {**TINY, "num_layers": 1, "attn_window": None}
    mm = counts.layer_matmul_params(cfg)
    attn = 4 * 4 * 16                    # per context slot
    head = 2 * 64 * 256
    # prompt of 3 (contexts 1, 2, 3), then 2 new tokens: the second one
    # needs a step at context 4; each new token needs one LM head
    want = 4 * 2 * mm + attn * (1 + 2 + 3 + 4) + 2 * head
    assert counts.request_flops(cfg, 3, 2) == want


def test_gcn_block_bytes_count_distinct_rows():
    cfg = {"features": 4}
    # 3 distinct sources read, 5 (src, dst) id pairs, 2 destinations written
    assert counts.gcn_block_bytes(cfg, 5, 3, 2) == 3 * 16 + 5 * 8 + 2 * 16


def test_gcn_distinct_counts_on_the_device():
    import jax.numpy as jnp
    drv = smoke_cell("gcn.paper").driver()
    src = jnp.asarray([5, 1, 5, 5, 2, 1, 9, 9], jnp.int32)
    dst = jnp.asarray([0, 0, 1, 1, 1, 3, 3, 3], jnp.int32)
    state = drv.State(data={"src": src, "dst": dst}, step=None, blocks=2)
    assert drv.distinct(state, 4, 0) == (2, 2)     # {1,5}; {0,1}
    assert drv.distinct(state, 4, 1) == (3, 2)     # {1,2,9}; {1,3}


def test_peaks_table_has_v5e_and_refuses_unknown_chips():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks("TPU v9 imaginary")


def test_closed_loop_batches_give_every_seed_the_same_work():
    cell = smoke_cell("danube.chat")
    gen = cell.generator()
    a = gen.make(cell.traffic, 256, 1)
    b = gen.make(cell.traffic, 256, 2**40 + 3)

    def shapes(pool):     # per batch, in order: prompt lengths, answers
        return [(sorted(len(p) for p, _ in batch),
                 sorted(m for _, m in batch)) for batch in pool]

    assert shapes(a) == shapes(b)
    assert all(sorted(m for _, m in batch) == [3] * 6 + [6] * 2
               for batch in a)
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a[0], b[0]))
    again = gen.make(cell.traffic, 256, 1)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for bx, by in zip(a, again) for x, y in zip(bx, by))
