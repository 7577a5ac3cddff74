"""Production training driver: data → step → checkpoint → restart.

Fault-tolerance posture (exercised by tests/examples on CPU, designed for
multi-pod):
  * batches are pure functions of (seed, step) — no pipeline state;
  * async sharded checkpoints every ``--ckpt-every`` steps, atomic rename;
  * on start, the driver resumes from the latest valid checkpoint and
    *re-shards* it onto whatever mesh the surviving fleet forms
    (``runtime.elastic`` plans the mesh, ``checkpoint`` re-distributes);
  * a step-time watchdog flags stragglers; the default policy checkpoints
    and exits with a rescale plan for the scheduler to act on.

Usage (CPU smoke):
  python -m repro.launch.train --arch yi-34b --smoke --steps 20
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, ShapeConfig, get_arch
from repro.data.synthetic import SyntheticDataset
from repro.launch.compile_cache import use_compile_cache
from repro.models.lm import build_lm
from repro.models.sharding import named_shardings
from repro.optim.adamw import (OptimizerConfig, adamw_update, init_opt_state,
                               opt_state_specs)
from repro.runtime import StepWatchdog, plan_rescale


def make_train_step(lm, opt_cfg: OptimizerConfig):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss, has_aux=True)(params, batch)
        params, opt_state, om = adamw_update(grads, opt_state, params,
                                             opt_cfg)
        return params, opt_state, {"loss": loss, **metrics, **om}
    return train_step


@dataclasses.dataclass
class TrainerConfig:
    arch: str = "yi-34b"
    shape: str = "train_4k"
    smoke: bool = False
    steps: int = 100
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    batch_override: Optional[int] = None
    seq_override: Optional[int] = None
    arch_overrides: Optional[dict] = None   # ArchConfig field replacements
    log_every: int = 10
    opt: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)


class Trainer:
    """Owns mesh, state, data and the restart loop."""

    def __init__(self, tc: TrainerConfig, mesh=None):
        self.tc = tc
        cfg = get_arch(tc.arch, smoke=tc.smoke)
        if tc.arch_overrides:
            cfg = dataclasses.replace(cfg, **tc.arch_overrides)
        shape = SHAPES[tc.shape]
        if tc.seq_override or tc.batch_override:
            shape = ShapeConfig(
                name="custom", kind="train",
                seq_len=tc.seq_override or shape.seq_len,
                global_batch=tc.batch_override or shape.global_batch)
        self.shape = shape
        self.mesh = mesh
        self.lm = build_lm(cfg, mesh, global_batch=shape.global_batch)
        self.cfg = cfg
        self.data = SyntheticDataset(cfg, shape, seed=tc.seed,
                                     batch_override=tc.batch_override)
        self.watchdog = StepWatchdog()
        self.ckpt = (CheckpointManager(tc.ckpt_dir, save_every=tc.ckpt_every)
                     if tc.ckpt_dir else None)

        step_fn = make_train_step(self.lm, tc.opt)
        ospecs = None
        if mesh is not None:
            pspecs = self.lm.param_specs()
            ospecs = named_shardings(mesh, opt_state_specs(pspecs))
            self.step_fn = jax.jit(step_fn,
                                   in_shardings=(
                                       named_shardings(mesh, pspecs),
                                       ospecs, None),
                                   donate_argnums=(0, 1))
        else:
            self.step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        # Adam moments are created where they live (in their shards on a
        # mesh), never whole on one device first.
        self._init_opt = jax.jit(init_opt_state, out_shardings=ospecs)

    # -- state ---------------------------------------------------------------
    def init_state(self):
        params = self.lm.init(jax.random.key(self.tc.seed))
        return params, self._init_opt(params), 0

    def restore_or_init(self):
        params, opt_state, start = self.init_state()
        if self.ckpt:
            tree = {"params": params, "opt": opt_state}
            specs = None
            if self.mesh is not None:
                p = self.lm.param_specs()
                specs = {"params": p, "opt": opt_state_specs(p)}
            step, restored = self.ckpt.restore_latest(tree, mesh=self.mesh,
                                                      specs=specs)
            if step is not None:
                print(f"[train] resumed from step {step}")
                return restored["params"], restored["opt"], step
        return params, opt_state, start

    # -- loop ----------------------------------------------------------------
    def run(self) -> Dict[str, Any]:
        params, opt_state, start = self.restore_or_init()
        history = []
        for step in range(start, self.tc.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in self.data.batch_at(step).items()}
            self.watchdog.start()
            params, opt_state, metrics = self.step_fn(params, opt_state,
                                                      batch)
            loss = float(metrics["loss"])
            alert = self.watchdog.stop(step)
            history.append(loss)
            if step % self.tc.log_every == 0:
                print(f"[train] step={step} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e}")
            if alert is not None:
                print(f"[train] STRAGGLER step={alert.step} "
                      f"x{alert.ratio:.1f} baseline "
                      f"{alert.baseline_s * 1e3:.0f}ms — checkpoint + "
                      "rescale plan:")
                if self.mesh is not None:
                    plan = plan_rescale(
                        tuple(self.mesh.shape.values()),
                        tuple(self.mesh.axis_names),
                        available_devices=len(jax.devices()),
                        global_batch=self.shape.global_batch)
                    print("[train]   " + plan.describe())
            if self.ckpt:
                self.ckpt.maybe_save(step + 1,
                                     {"params": params, "opt": opt_state})
        if self.ckpt:
            self.ckpt.wait()
        return {"final_loss": history[-1] if history else None,
                "history": history,
                "median_step_s": self.watchdog.median_step_s,
                "params": params}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-34b")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    tc = TrainerConfig(arch=args.arch, shape=args.shape, smoke=args.smoke,
                       steps=args.steps, batch_override=args.batch,
                       seq_override=args.seq, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, seed=args.seed)
    out = Trainer(tc).run()
    print(f"[train] done: final_loss={out['final_loss']:.4f} "
          f"median_step={out['median_step_s'] * 1e3:.0f}ms")


if __name__ == "__main__":
    main()
