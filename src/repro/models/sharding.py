"""Logical-axis sharding rules (t5x/MaxText style, minimal).

Arrays are annotated with *logical* axis names; ``Rules`` maps them onto
mesh axes. One place to retarget the whole framework when the mesh changes
(single-pod ``(data, model)`` vs multi-pod ``(pod, data, model)``), when a
shape degenerates (``long_500k`` has batch=1 — batch can't shard), or when
a hillclimb wants a different layout (e.g. expert-parallel MoE).

Conventions:
  activations: batch/seq/embed/heads/kv_seq
  weights:     w_fsdp (ZeRO-3 shard dim), w_tp (tensor-parallel dim),
               w_vocab_tp (vocab-sharded head), expert (MoE expert dim)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    batch: Axis = ("pod", "data")
    seq: Axis = None
    embed: Axis = None          # activation d_model: replicated (Megatron)
    heads: Axis = "model"
    kv_heads: Axis = None       # only sharded when divisible by the TP axis
    kv_seq: Axis = "model"      # decode KV cache: flash-decoding split
    vocab: Axis = "model"
    expert_capacity: Axis = "data"
    w_fsdp: Axis = "data"       # ZeRO-3: shard weights, all-gather at use
    w_tp: Axis = "model"        # Megatron TP dim
    w_vocab_tp: Axis = "model"
    expert: Axis = None         # MoE expert dim ("model" under EP)
    expert_in: Axis = "data"    # expert-weight d_model dim (FSDP under TP)
    expert_out: Axis = "model"  # expert-weight FFN dim (TP); None under EP
    layers: Axis = None         # stacked-scan leading dim

    def spec(self, *logical: Optional[str]) -> P:
        parts = []
        for name in logical:
            if name is None:
                parts.append(None)
            else:
                parts.append(getattr(self, name))
        return P(*parts)


def make_rules(mesh: Optional[Mesh], *, global_batch: int = 0,
               moe_strategy: str = "tp", num_kv_heads: int = 0,
               num_heads: int = 0) -> Rules:
    """Build rules adapted to the mesh topology and workload shape.

    Head dims are only mapped to the TP axis when they divide it — a
    non-divisible constraint (8 KV heads on a 16-way axis) makes GSPMD
    invent split layouts that force involuntary full rematerialization.
    """
    if mesh is None:
        # Single-device tests: everything replicated.
        return Rules(batch=None, heads=None, kv_seq=None, vocab=None,
                     w_fsdp=None, w_tp=None, w_vocab_tp=None,
                     expert_capacity=None, expert_in=None, expert_out=None)
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    batch: Axis = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    batch_size_on_mesh = 1
    for a in (batch_axes or ()):
        batch_size_on_mesh *= mesh.shape[a]
    kv_seq: Axis = "model"
    cap: Axis = "data" if "data" in names else None
    if global_batch and global_batch < batch_size_on_mesh:
        # Degenerate batch (long_500k B=1): free the batch axes and use them
        # for the KV/state sequence dim instead.
        batch = None
        kv_seq = tuple(a for a in ("data", "model") if a in names)
        cap = None
    expert: Axis = None
    expert_in: Axis = "data"
    expert_out: Axis = "model"
    if moe_strategy == "ep":
        # shard_map all-to-all dispatch (models/moe_ep.py): experts live
        # whole on their owner shard, replicated over data
        expert, expert_in, expert_out = "model", None, None
    tp = mesh.shape.get("model", 1)
    heads_ax: Axis = "model" if (num_heads == 0 or num_heads % tp == 0) \
        else None
    kv_ax: Axis = "model" if (num_kv_heads and num_kv_heads % tp == 0) \
        else None
    return Rules(batch=batch, kv_seq=kv_seq, expert=expert,
                 expert_in=expert_in, expert_out=expert_out,
                 expert_capacity=cap, heads=heads_ax, kv_heads=kv_ax)


def serving_weight_overrides(cfg, global_batch: int,
                             mesh: Optional[Mesh]) -> dict:
    """Rule overrides for the serve path (§Perf, granite-decode hillclimb).

    Batched *dense* decode replicates weights across the data axis — the
    per-step ZeRO-3 all-gathers (11 GB/dev/step measured on granite) cost
    more than the extra HBM reads. Batch-1 long-context decode and MoE
    serving keep 2D (FSDP x TP) weight sharding: with tiny activations the
    psum'd 256-way-sharded matmuls read 16x less weight per device, which
    measured 5-25x better on long_500k, and MoE expert weights are too
    large to replicate profitably.
    """
    if mesh is None or cfg.moe is not None:
        return {}
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    return {"w_fsdp": None} if global_batch >= dp else {}


def shard(x, rules: Rules, *logical, mesh: Optional[Mesh] = None):
    """with_sharding_constraint by logical names (no-op off-mesh)."""
    if mesh is None or not _in_jit():
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, rules.spec(*logical)))


def _in_jit() -> bool:
    return True  # constraints are harmless outside jit in recent JAX


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """The repo's one mesh constructor: the first ``prod(shape)`` devices
    with ``Auto`` axes. ``jax.make_mesh`` defaults to ``Explicit`` axes,
    which reject the logical ``with_sharding_constraint``s of ``shard``."""
    need = math.prod(shape)
    devices = jax.devices() if devices is None else devices
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {tuple(shape)} needs {need} devices, have {len(devices)}")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=list(devices)[:need])


def named_shardings(mesh: Mesh, spec_tree):
    """``NamedSharding`` on ``mesh`` for every PartitionSpec of a tree."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
