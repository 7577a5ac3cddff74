"""Every Pallas kernel compiles for a TPU v5e at h2o-danube-1.8b widths.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets XLA and Mosaic compile (not run) each kernel, and
refuse what the chip would refuse — block shapes off the (8, 128) tiling,
slices of tiled VMEM axes, loads from memory spaces a kernel cannot load
from. Interpret-mode tests cannot see any of that.

Widths: the h2o-danube-1.8b embedding table (32000 x 2560 bf16), 4096
requests, the default controller cache and DMA configs, and attention at
32 heads x 80 head dim over 512 tokens. The topology is described inside
a fixture only: describing it loads the TPU library, which one process at
a time may hold.

The row kernels must also read the table where it lies: a relayout of
the 32000 x 2560 table for the kernel (a ``copy`` or ``transpose`` of
it, or a table-sized temporary) would cost more HBM traffic than the
gather itself and would undo the scatter's in-place update.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import CacheConfig, DMAConfig

ROWS, D, N = 32000, 2560, 4096


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # Loading the TPU library would otherwise write its logs under /tmp
    # (with TPU_LOG_DIR=disabled too); keep them in this run's own tmp.
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _compiled_text(fn, *args) -> str:
    return _compile(fn, *args).as_text()


def _gather_program(one_chip):
    from repro.kernels.sorted_gather.kernel import gather_rows
    return _compile(
        lambda t, i: gather_rows(t, i, interpret=False),
        _spec((ROWS, D), jnp.bfloat16, one_chip),
        _spec((N,), jnp.int32, one_chip))


def _scatter_program(one_chip):
    from repro.kernels.sorted_scatter.kernel import scatter_rows
    return _compile(
        lambda t, i, v: scatter_rows(t, i, v, interpret=False),
        _spec((ROWS, D), jnp.bfloat16, one_chip),
        _spec((N,), jnp.int32, one_chip),
        _spec((N, D), jnp.bfloat16, one_chip), donate=(0,))


def _gather(one_chip):
    return _gather_program(one_chip).as_text()


def _scatter(one_chip):
    return _scatter_program(one_chip).as_text()


def _bitonic(one_chip):
    from repro.kernels.bitonic_sort.kernel import bitonic_sort_batched
    return _compiled_text(
        lambda k, v: bitonic_sort_batched(k, v, interpret=False),
        _spec((1, N), jnp.int32, one_chip),
        _spec((1, N), jnp.int32, one_chip))


def _dma(one_chip):
    from repro.kernels.dma_copy.kernel import dma_copy_chunked
    cfg = DMAConfig()
    rows = cfg.max_transaction_bytes // (2 * 128)       # bf16 tile stack
    chunks = ROWS * D // (rows * 128)
    return _compiled_text(
        lambda s: dma_copy_chunked(s, channels=cfg.num_parallel_dma,
                                   interpret=False),
        _spec((chunks, rows, 128), jnp.bfloat16, one_chip))


def _cache(one_chip):
    from repro.kernels.cache_lookup.kernel import cache_probe
    cfg = CacheConfig()
    state = (cfg.num_lines // cfg.associativity, cfg.associativity)
    return _compiled_text(
        lambda l, t, v, a, c: cache_probe(l, t, v, a, c, interpret=False),
        _spec((N,), jnp.int32, one_chip),
        *(_spec(state, jnp.int32, one_chip) for _ in range(3)),
        _spec((), jnp.int32, one_chip))


def _flash(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    heads, kv_heads, seq, hd = 32, 8, 512, 80
    return _compiled_text(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, group=heads // kv_heads, causal=True, window=4096,
            interpret=False),
        _spec((heads, seq, hd), jnp.bfloat16, one_chip),
        _spec((kv_heads, seq, hd), jnp.bfloat16, one_chip),
        _spec((kv_heads, seq, hd), jnp.bfloat16, one_chip))


@pytest.mark.parametrize("build", [_gather, _scatter, _bitonic, _dma,
                                   _cache, _flash],
                         ids=["sorted_gather", "sorted_scatter",
                              "bitonic_sort", "dma_copy", "cache_lookup",
                              "flash_attention"])
def test_kernel_compiles_for_v5e(build, one_chip):
    assert "tpu_custom_call" in build(one_chip)


@pytest.mark.parametrize("program,aliased",
                         [(_gather_program, 0),
                          (_scatter_program, ROWS * D * 2)],
                         ids=["sorted_gather", "sorted_scatter"])
def test_row_kernel_reads_table_in_place(program, aliased, one_chip):
    compiled = program(one_chip)
    table_shape = re.compile(rf"= \w+\[{ROWS},[^=]*\b(copy|transpose)\(")
    copies = [line.strip() for line in compiled.as_text().splitlines()
              if table_shape.search(line)]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < ROWS * D * 2
    # The donated table is the scatter's output buffer: updated in place.
    assert mem.alias_size_in_bytes == aliased


def test_served_decode_updates_the_cache_in_place(one_chip, monkeypatch):
    """``Server``'s decode at h2o-danube-1.8b widths (batch 8, 4096 KV
    slots) takes its donated cache as its output buffer and writes each
    layer's new row there: no whole layer's K or V is written into the
    stack, and the stack is never copied."""
    from repro.launch.serve import Server
    from repro.models.lm import LM
    monkeypatch.setattr(LM, "init", lambda self, key: self.abstract_params())
    server = Server("h2o-danube-1.8b")

    def put(t):
        return _spec(t.shape, t.dtype, one_chip)

    cache = jax.tree.map(put, server.lm.init_cache(8, 4096, abstract=True))
    stack = (24, 8, 4096, 8, 80)
    assert {leaf.shape for leaf in jax.tree.leaves(cache)} == {stack}
    compiled = server._decode.lower(
        jax.tree.map(put, server.params), _spec((8,), jnp.int32, one_chip),
        cache, _spec((), jnp.int32, one_chip)).compile()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree.leaves(cache))
    assert cache_bytes == 2_013_265_920
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes

    text = compiled.as_text()
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    stacked = ",".join(map(str, stack))
    updates = re.findall(rf"= \w+\[{stacked}\]\S* dynamic-update-slice\("
                         r"%[\w.\-]+, %([\w.\-]+)", text)
    assert updates
    for name in updates:
        slots = int(shapes[name].split(",")[-3])
        assert slots < 4096, (name, shapes[name])
    copies = re.findall(rf"= \w+\[{stacked}\]\S* copy\(", text)
    assert not copies, copies
