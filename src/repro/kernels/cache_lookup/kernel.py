"""Cache-engine tag/LRU pipeline as a Pallas kernel (paper §IV-A, Fig. 3/4).

The FPGA cache engine runs a 4-stage PE pipeline (tag read → compare → LRU
decision → data access) and a 3-stage MEM fill pipeline sharing Tag RAM,
Data RAM and LRU state; shared-RAM hazards force one beat at a time. The
TPU kernel keeps the whole tag store + LRU age matrix in SMEM and walks the
request batch with a ``fori_loop`` on the scalar core — the sequential loop
*is* the shared-RAM stall semantics — comparing the ways of a set in one
unrolled step, as the FPGA compares all ways in parallel.

The kernel owns metadata only (tags/valid/age → hit?, way). The data path
(serving hit lines from the VMEM-resident Data RAM, filling victims from
HBM) is composed around it in ``ops.py`` — mirroring the paper's split
between the tag pipelines and the Data RAM port.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _cache_probe_kernel(line_ids_ref, tags_ref, valid_ref, age_ref,
                        clock_ref, hits_ref, ways_ref, out_tags_ref,
                        out_valid_ref, out_age_ref, out_clock_ref, *,
                        num_sets: int, ways: int):
    n = line_ids_ref.shape[0]

    # Copy-in the shared state (Tag RAM / valid bits / LRU ages).
    def copy_in(k, carry):
        out_tags_ref[k] = tags_ref[k]
        out_valid_ref[k] = valid_ref[k]
        out_age_ref[k] = age_ref[k]
        return carry

    jax.lax.fori_loop(0, num_sets * ways, copy_in, 0)

    def beat(i, clock):
        line = line_ids_ref[i]
        base = (line % num_sets) * ways
        tag = line // num_sets

        # Compare every way (the FPGA's parallel tag compare), keeping
        # the first matching way and the first least-recently-used one
        # (invalid ways carry age -1, so they are chosen first).
        hit = jnp.bool_(False)
        hit_way = victim = jnp.int32(0)
        victim_age = out_age_ref[base]
        for w in range(ways):
            match = (out_valid_ref[base + w] != 0) & (
                out_tags_ref[base + w] == tag)
            hit_way = jnp.where(match & ~hit, jnp.int32(w), hit_way)
            hit = hit | match
            age = out_age_ref[base + w]
            older = age < victim_age
            victim = jnp.where(older, jnp.int32(w), victim)
            victim_age = jnp.where(older, age, victim_age)
        way = jnp.where(hit, hit_way, victim)

        hits_ref[i] = hit.astype(jnp.int32)
        ways_ref[i] = way
        out_tags_ref[base + way] = tag
        out_valid_ref[base + way] = jnp.int32(1)
        out_age_ref[base + way] = clock + 1   # stamp after advancing
        return clock + 1

    out_clock_ref[0] = jax.lax.fori_loop(0, n, beat, clock_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_probe(line_ids: jnp.ndarray, tags: jnp.ndarray,
                valid: jnp.ndarray, age: jnp.ndarray, clock: jnp.ndarray,
                *, interpret: bool | None = None):
    """Run a request batch through the tag/LRU pipeline.

    Returns (hits (N,), way (N,), tags', valid', age', clock'). The
    request ids and the state live in SMEM, flattened set-major, and the
    beats are scalar-core work; the default Table I config (4096 lines)
    is 48 KiB of metadata. ``interpret=None`` interprets on the CPU
    backend only.
    """
    n = line_ids.shape[0]
    sets, ways = tags.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    state = jax.ShapeDtypeStruct((sets * ways,), jnp.int32)
    hits, way, tags2, valid2, age2, clock2 = pl.pallas_call(
        functools.partial(_cache_probe_kernel, num_sets=sets, ways=ways),
        in_specs=[smem] * 5,
        out_specs=(smem,) * 6,
        out_shape=(
            jax.ShapeDtypeStruct((n,), jnp.int32),          # hits
            jax.ShapeDtypeStruct((n,), jnp.int32),          # ways
            state, state, state,                    # tags', valid', age'
            jax.ShapeDtypeStruct((1,), jnp.int32),          # clock'
        ),
        interpret=interpret_default() if interpret is None else interpret,
    )(line_ids.astype(jnp.int32), tags.reshape(-1), valid.reshape(-1),
      age.reshape(-1), clock.reshape(1).astype(jnp.int32))
    shape = (sets, ways)
    return (hits, way, tags2.reshape(shape), valid2.reshape(shape),
            age2.reshape(shape), clock2)
