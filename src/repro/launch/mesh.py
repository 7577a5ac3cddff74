"""The production mesh.

Defined as a function (never a module-level constant) so importing this
module can never touch jax device state — required for the dry-run's
XLA_FLAGS ordering contract.

Topology: one v5e pod contributes a 16x16 (data, model) mesh (256 chips);
multi-pod prepends a pure-DP ``pod`` axis (2x16x16 = 512 chips). Meshes
of any other shape, such as the elastic runtime's after a failure, come
from the same constructor, ``models.sharding.make_mesh``.
"""

from __future__ import annotations

from typing import Optional, Sequence

from repro.models.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence] = None):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes, devices)
