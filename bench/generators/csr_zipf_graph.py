"""A graph for GCN aggregation, made on the device from the seed.

From the configuration: ``vertices`` rows of ``features`` float32 values
(a normal draw), and ``edge_visits`` (source, destination) int32 pairs
stored sorted by destination, as a CSR walk reads them. Destinations are
uniform over the vertices. Sources are Zipf-popular
(``src_zipf_exponent`` s) over a seeded permutation of the vertices,
drawn as a discretised Pareto truncated to the vertex count: with
a = s - 1 and u uniform, rank = floor((1 - u (1 - V^-a))^(-1/a)) - 1.
This is the popularity skew that ``benchmarks/fig7_workloads.py`` draws
with ``numpy.random.zipf(1.2)`` for its cut-down trace.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("vertices", "features"))
def _table(key, vertices: int, features: int):
    return jax.random.normal(key, (vertices, features), jnp.float32)


@partial(jax.jit, static_argnames=("vertices", "edges", "exponent"))
def _edges(key, vertices: int, edges: int, exponent: float):
    k_dst, k_src, k_perm = jax.random.split(key, 3)
    dst = jnp.sort(jax.random.randint(k_dst, (edges,), 0, vertices,
                                      jnp.int32))
    a = exponent - 1.0
    u = jax.random.uniform(k_src, (edges,), jnp.float32)
    x = (1.0 - u * (1.0 - vertices ** -a)) ** (-1.0 / a)
    rank = jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, vertices - 1)
    perm = jax.random.permutation(k_perm, vertices).astype(jnp.int32)
    return jnp.take(perm, rank), dst


def make(config: dict, key) -> dict:
    """{"table", "src", "dst"} on the default device."""
    k_table, k_edges = jax.random.split(key)
    table = _table(k_table, config["vertices"], config["features"])
    src, dst = _edges(k_edges, config["vertices"], config["edge_visits"],
                      config["src_zipf_exponent"])
    return {"table": table, "src": src, "dst": dst}
