import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede every other import (jax locks the device
count at first init); 512 placeholder host devices back both the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh.

Per cell this driver:
  1. builds the jitted entry point (train_step / serve_prefill / serve_step)
     with NamedSharding in/out specs,
  2. ``.lower().compile()`` — success proves the sharding config is
     coherent (no mismatched specs, no unsupported collective, no
     compile-time OOM),
  3. records ``memory_analysis()`` + ``cost_analysis()``,
  4. extracts roofline terms. XLA cost analysis counts while-loop bodies
     once, so scanned-layer costs are *extrapolated exactly*: two small
     unrolled variants (1 and 2 layer-groups) are also compiled and the
     per-group cost is their difference:
         total = cost(G1) + (num_groups - 1) * (cost(G2) - cost(G1)).
     The full scanned compile remains the compile-proof + memory source.

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""

import argparse
import dataclasses
import json
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_arch, supported_shapes
from repro.configs.registry import ARCH_IDS, canonical
from repro.data.synthetic import batch_specs
from repro.launch import roofline
from repro.launch.mesh import make_production_mesh
from repro.models.lm import build_lm
from repro.models.sharding import named_shardings
from repro.optim.adamw import (OptimizerConfig, abstract_opt_state,
                               adamw_update, opt_state_specs)

MARGIN = 256   # decode cache slack; multiple of 256 keeps seq-sharding even


def estimate_state_bytes_per_device(abstract_tree, spec_tree, mesh) -> float:
    """Analytic per-device bytes of a sharded pytree (params/opt/cache)."""
    total = 0.0
    for leaf, spec in zip(jax.tree.leaves(abstract_tree),
                          jax.tree.leaves(
                              spec_tree,
                              is_leaf=lambda x: isinstance(x, P))):
        shard_elems = float(np.prod(leaf.shape)) if leaf.shape else 1.0
        for axis_entry in spec:
            if axis_entry is None:
                continue
            axes = (axis_entry,) if isinstance(axis_entry, str) \
                else axis_entry
            for ax in axes:
                shard_elems /= mesh.shape[ax]
        total += shard_elems * jnp.dtype(leaf.dtype).itemsize
    return total


def build_cell(arch_name: str, shape_name: str, mesh, *,
               moe_strategy: str = "tp", overrides: Dict[str, Any] = None,
               sharding_overrides: Dict[str, Any] = None):
    """Returns (jitted_fn, abstract_args, state_bytes_per_device, cfg)."""
    cfg = get_arch(arch_name)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    lm = build_lm(cfg, mesh, global_batch=shape.global_batch,
                  moe_strategy=moe_strategy)
    if sharding_overrides is None and shape.kind == "decode":
        # production serving layout (see sharding.serving_weight_overrides)
        from repro.models.sharding import serving_weight_overrides
        sharding_overrides = serving_weight_overrides(
            cfg, shape.global_batch, mesh)
    if sharding_overrides:
        # e.g. {"w_fsdp": None} — serving replicates weights across the
        # data axis instead of gathering them every decode step (§Perf).
        lm.rules = dataclasses.replace(lm.rules, **sharding_overrides)
    rules = lm.rules
    pspecs = lm.param_specs()
    aparams = lm.abstract_params()
    state_bytes = estimate_state_bytes_per_device(aparams, pspecs, mesh)

    if shape.kind == "train":
        opt_cfg = OptimizerConfig()
        ospecs = opt_state_specs(pspecs)
        aopt = abstract_opt_state(aparams)
        bshapes, bspecs = batch_specs(cfg, shape, rules)
        state_bytes += estimate_state_bytes_per_device(aopt, ospecs, mesh)

        def train_step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lm.loss, has_aux=True)(params, batch)
            params, opt_state, om = adamw_update(grads, opt_state, params,
                                                 opt_cfg)
            return params, opt_state, loss, {**metrics, **om}

        fn = jax.jit(
            train_step,
            in_shardings=(named_shardings(mesh, pspecs),
                          named_shardings(mesh, ospecs),
                          named_shardings(mesh, bspecs)),
            donate_argnums=(0, 1),
        )
        args = (aparams, aopt, bshapes)

    elif shape.kind == "prefill":
        bshapes, bspecs = batch_specs(cfg, shape, rules)
        bshapes.pop("labels"), bspecs.pop("labels")
        cspecs = lm.cache_specs()

        def serve_prefill(params, batch):
            logits, cache, cur = lm.prefill(params, batch,
                                            max_len=shape.seq_len + MARGIN)
            return logits, cache, cur

        fn = jax.jit(
            serve_prefill,
            in_shardings=(named_shardings(mesh, pspecs),
                          named_shardings(mesh, bspecs)),
            out_shardings=(None, named_shardings(mesh, cspecs), None),
        )
        args = (aparams, bshapes)

    else:   # decode
        B = shape.global_batch
        acache = lm.init_cache(B, shape.seq_len + MARGIN, abstract=True)
        cspecs = lm.cache_specs()
        state_bytes += estimate_state_bytes_per_device(acache, cspecs, mesh)

        def serve_step(params, token, cache, cur_len):
            return lm.decode_step(params, token, cache, cur_len)

        fn = jax.jit(
            serve_step,
            in_shardings=(named_shardings(mesh, pspecs),
                          NamedSharding(mesh, rules.spec("batch")),
                          named_shardings(mesh, cspecs),
                          NamedSharding(mesh, P())),
            donate_argnums=(2,),
        )
        args = (aparams,
                jax.ShapeDtypeStruct((B,), jnp.int32),
                acache,
                jax.ShapeDtypeStruct((), jnp.int32))

    return fn, args, state_bytes, cfg, shape


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool = False,
             moe_strategy: str = "tp", skip_extrapolation: bool = False,
             overrides: Dict[str, Any] = None,
             sharding_overrides: Dict[str, Any] = None) -> Dict[str, Any]:
    arch_name = canonical(arch_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    cell = f"{arch_name}/{shape_name}/{'2pod' if multi_pod else '1pod'}"
    rec: Dict[str, Any] = {"cell": cell, "chips": chips,
                           "moe_strategy": moe_strategy}

    t0 = time.time()
    fn, args, state_bytes, cfg, shape = build_cell(
        arch_name, shape_name, mesh, moe_strategy=moe_strategy,
        overrides=overrides, sharding_overrides=sharding_overrides)
    lowered = fn.lower(*args)
    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    # --- memory ---
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: getattr(ma, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    except Exception as e:      # pragma: no cover - backend specific
        rec["memory_analysis"] = {"error": str(e)}
    rec["state_bytes_per_device"] = state_bytes

    # --- cost extrapolation over layer groups ---
    period = cfg.scan_period
    groups = cfg.num_layers // period
    if skip_extrapolation or groups <= 2:
        reports = [roofline.analyze("full", compiled, chips=chips,
                                    model_flops=0.0)]
        flops, hbm, coll = (reports[0].hlo_flops, reports[0].hbm_bytes,
                            reports[0].collective_bytes)
        det = reports[0].collectives_detail
    else:
        sub = {}
        for g in (1, 2):
            sfn, sargs, _, _, _ = build_cell(
                arch_name, shape_name, mesh, moe_strategy=moe_strategy,
                overrides={**(overrides or {}),
                           "num_layers": g * period, "scan_layers": False},
                sharding_overrides=sharding_overrides)
            scomp = sfn.lower(*sargs).compile()
            sub[g] = roofline.analyze(f"G{g}", scomp, chips=chips,
                                      model_flops=0.0)
        flops = sub[1].hlo_flops + (groups - 1) * (
            sub[2].hlo_flops - sub[1].hlo_flops)
        hbm = sub[1].hbm_bytes + (groups - 1) * (
            sub[2].hbm_bytes - sub[1].hbm_bytes)
        coll = sub[1].collective_bytes + (groups - 1) * (
            sub[2].collective_bytes - sub[1].collective_bytes)
        det = {k: sub[1].collectives_detail[k] + (groups - 1) * (
            sub[2].collectives_detail[k] - sub[1].collectives_detail[k])
            for k in sub[1].collectives_detail}

    n_active = cfg.active_param_count()
    report = roofline.RooflineReport(
        name=cell, chips=chips, hlo_flops=flops, hbm_bytes=hbm,
        collective_bytes=coll, collectives_detail=det,
        model_flops=roofline.model_flops_for(cfg, shape, n_active),
        bytes_per_device=state_bytes)
    rec.update({
        "hlo_flops": flops, "hbm_bytes": hbm, "collective_bytes": coll,
        "collectives_detail": det,
        "model_flops": report.model_flops,
        "compute_s": report.compute_s, "memory_s": report.memory_s,
        "collective_s": report.collective_s,
        "bottleneck": report.bottleneck,
        "useful_flops_ratio": report.useful_flops_ratio,
        "roofline_fraction": report.roofline_fraction,
    })
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true",
                    help="run every supported (arch x shape) cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--moe-strategy", default="tp", choices=("tp", "ep"))
    ap.add_argument("--out", type=str, default=None,
                    help="append JSON records here")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in supported_shapes(get_arch(a)):
                cells.append((a, s))
    else:
        if not args.arch:
            ap.error("--arch or --all required")
        shapes = ([args.shape] if args.shape
                  else supported_shapes(get_arch(canonical(args.arch))))
        cells = [(args.arch, s) for s in shapes]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    for arch, shp in cells:
        for mp in meshes:
            try:
                rec = run_cell(arch, shp, multi_pod=mp,
                               moe_strategy=args.moe_strategy)
                status = "OK"
            except Exception as e:   # noqa: BLE001 - report and continue
                rec = {"cell": f"{canonical(arch)}/{shp}/"
                               f"{'2pod' if mp else '1pod'}",
                       "error": f"{type(e).__name__}: {e}"}
                status = "FAIL"
            print(f"[{status}] {rec['cell']}: "
                  + (f"compile={rec.get('compile_s')}s "
                     f"flops={rec.get('hlo_flops', 0):.3e} "
                     f"bottleneck={rec.get('bottleneck')}"
                     if status == "OK" else rec["error"]))
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
