"""Jitted public wrapper for the bitonic-sort scheduler kernel.

Handles non-power-of-two batch sizes by padding with a +inf sentinel key
(INT32_MAX), which sorts to the tail and is sliced off — matching the FPGA
scheduler's behaviour of issuing a partially filled batch at timeout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bitonic_sort.kernel import bitonic_sort_batched

_SENTINEL = jnp.iinfo(jnp.int32).max


def _next_pow2(n: int) -> int:
    return 1 << max(1, (n - 1).bit_length())


def sort_with_indices(keys: jnp.ndarray, vals: jnp.ndarray | None = None):
    """Stable-sort ``keys`` (1-D or (G, N)) via the Pallas network.

    Returns (sorted_keys, perm) when ``vals`` is None else
    (sorted_keys, perm, sorted_vals). ``perm`` indexes arrival order —
    apply it to payloads, invert it to unsort responses.
    """
    squeeze = keys.ndim == 1
    k2 = keys[None, :] if squeeze else keys
    v2 = (jnp.zeros_like(k2) if vals is None
          else (vals[None, :] if squeeze else vals))
    g, n = k2.shape
    n_pad = _next_pow2(n)
    if n_pad != n:
        k2 = jnp.pad(k2, ((0, 0), (0, n_pad - n)),
                     constant_values=_SENTINEL)
        v2 = jnp.pad(v2, ((0, 0), (0, n_pad - n)))
    skeys, perm, svals = bitonic_sort_batched(k2.astype(jnp.int32), v2)
    skeys, perm, svals = skeys[:, :n], perm[:, :n], svals[:, :n]
    if squeeze:
        skeys, perm, svals = skeys[0], perm[0], svals[0]
    if vals is None:
        return skeys, perm
    return skeys, perm, svals
