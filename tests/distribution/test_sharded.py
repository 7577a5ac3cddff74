"""Distribution tests on 8 fake CPU devices (subprocess: device count must
be set before jax initializes, and the main test process keeps 1 device).

Validates: (a) the sharded train step runs and matches the single-device
step numerically; (b) the dry-run cost-extrapolation methodology is exact
on a model small enough to fully unroll; (c) elastic restore onto a
different mesh preserves values.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_sub(body: str) -> dict:
    """Run `body` in a subprocess with 8 host devices; expects it to print
    a single JSON line prefixed RESULT:."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.models.sharding import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"    # the 8 host devices, never a chip
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT:")][-1]
    return json.loads(line[len("RESULT:"):])


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    res = run_sub("""
        from repro.configs import get_arch
        from repro.models.lm import build_lm
        from repro.optim.adamw import OptimizerConfig, adamw_update, \\
            init_opt_state, opt_state_specs
        from repro.data.synthetic import SyntheticDataset
        from repro.configs.base import ShapeConfig
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_arch("yi-34b", smoke=True)
        shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
        data = SyntheticDataset(cfg, shape, seed=0)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

        def step(lm, params, opt, batch):
            (loss, m), g = jax.value_and_grad(lm.loss, has_aux=True)(
                params, batch)
            params, opt, _ = adamw_update(g, opt, params,
                                          OptimizerConfig(warmup_steps=1))
            return loss, params

        # single device
        lm1 = build_lm(cfg)
        p1 = lm1.init(jax.random.key(0))
        o1 = init_opt_state(p1)
        loss1, p1n = jax.jit(lambda p, o, b: step(lm1, p, o, b))(p1, o1,
                                                                 batch)

        # 4x2 mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        lm2 = build_lm(cfg, mesh, global_batch=8)
        p2 = lm2.init(jax.random.key(0))
        o2 = init_opt_state(p2)
        named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        ps = lm2.param_specs()
        fn = jax.jit(lambda p, o, b: step(lm2, p, o, b),
                     in_shardings=(named(ps), named(opt_state_specs(ps)),
                                   None))
        loss2, p2n = fn(p2, o2, batch)
        dmax = max(float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(p1n), jax.tree.leaves(p2n)))
        print("RESULT:" + json.dumps(
            {"loss1": float(loss1), "loss2": float(loss2), "dmax": dmax}))
    """)
    assert abs(res["loss1"] - res["loss2"]) < 5e-3
    assert res["dmax"] < 5e-2


@pytest.mark.slow
def test_cost_extrapolation_exact_on_unrollable_model():
    """total = cost(G1) + (G-1)(cost(G2)-cost(G1)) must equal the cost of
    the fully-unrolled G-group model (the dry-run's core assumption)."""
    res = run_sub("""
        import dataclasses
        from repro.configs import get_arch
        from repro.launch import roofline
        from repro.launch.dryrun import build_cell
        import repro.launch.dryrun as dr

        mesh = make_mesh((2, 2), ("data", "model"))

        def cost_for(nl, scan):
            fn, args, _, _, _ = build_cell(
                "h2o-danube-1.8b", "train_4k", mesh,
                overrides={"num_layers": nl, "scan_layers": scan,
                           "d_model": 64, "num_heads": 4,
                           "num_kv_heads": 2, "d_ff": 128,
                           "vocab_size": 256, "head_dim": 16,
                           "attn_window": 8})
            comp = fn.lower(*args).compile()
            return roofline.analyze("x", comp, chips=4, model_flops=0)

        g1 = cost_for(1, False)
        g2 = cost_for(2, False)
        g6 = cost_for(6, False)            # ground truth, unrolled
        extrap = g1.hlo_flops + 5 * (g2.hlo_flops - g1.hlo_flops)
        extrap_coll = g1.collective_bytes + 5 * (
            g2.collective_bytes - g1.collective_bytes)
        print("RESULT:" + json.dumps({
            "true": g6.hlo_flops, "extrap": extrap,
            "true_coll": g6.collective_bytes,
            "extrap_coll": extrap_coll}))
    """)
    assert res["true"] > 0
    # Error bars measured on this deliberately tiny config (d=64): ~6-9%
    # FLOPs, ~15% collectives — fusion boundaries and XLA's
    # depth-dependent collective combining are a visible share at toy
    # scale. At production scale the uniform layer term is >99% of cost.
    # These bounds are documented in EXPERIMENTS.md's methodology note.
    assert abs(res["extrap"] - res["true"]) / res["true"] < 0.12
    if res["true_coll"] > 0:
        assert abs(res["extrap_coll"] - res["true_coll"]) \
            / res["true_coll"] < 0.20


@pytest.mark.slow
def test_ep_dispatch_matches_tp_and_trains():
    """Expert-parallel (shard_map all_to_all) MoE must value-match the TP
    dispatch and run a full sharded train step."""
    res = run_sub("""
        import dataclasses
        from repro.configs import get_arch
        from repro.models import blocks
        from repro.models.lm import build_lm
        from repro.models.moe_ep import moe_ffn_ep
        from repro.models.sharding import make_rules
        from repro.optim.adamw import OptimizerConfig, adamw_update, \\
            init_opt_state, opt_state_specs
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_arch("jamba-v0.1-52b", smoke=True)
        cfgf = dataclasses.replace(
            cfg, param_dtype="float32",
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        lm0 = build_lm(cfgf)
        params0 = lm0.init(jax.random.key(0))
        pos = next(k for k, v in params0["layers"].items() if "moe" in v)
        p = jax.tree.map(lambda t: t[0], params0["layers"][pos]["moe"])
        x = jax.random.normal(jax.random.key(2), (4, 16, cfgf.d_model),
                              jnp.float32)
        want, _ = blocks.moe_ffn(p, x, cfgf, make_rules(None), None)
        with mesh:
            got, _ = jax.jit(lambda p, x: moe_ffn_ep(p, x, cfgf, mesh))(p, x)
        err = float(jnp.max(jnp.abs(got - want)))

        lm = build_lm(cfg, mesh, global_batch=8, moe_strategy="ep")
        params = lm.init(jax.random.key(0))
        opt = init_opt_state(params)
        toks = jax.random.randint(jax.random.key(1), (8, 32), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda s: isinstance(s, P))
        def step(p, o, b):
            (loss, m), g = jax.value_and_grad(lm.loss, has_aux=True)(p, b)
            p, o, _ = adamw_update(g, o, p, OptimizerConfig(warmup_steps=1))
            return loss
        ps = lm.param_specs()
        loss = jax.jit(step, in_shardings=(named(ps),
                                           named(opt_state_specs(ps)),
                                           None))(params, opt, batch)
        print("RESULT:" + json.dumps({"err": err, "loss": float(loss)}))
    """)
    assert res["err"] < 1e-4
    assert np.isfinite(res["loss"])


@pytest.mark.slow
def test_elastic_restore_onto_smaller_mesh(tmp_path):
    res = run_sub(f"""
        from repro.configs import get_arch
        from repro.models.lm import build_lm
        from repro.checkpoint import save_checkpoint, load_checkpoint
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_arch("yi-34b", smoke=True)
        mesh8 = make_mesh((4, 2), ("data", "model"))
        lm = build_lm(cfg, mesh8)
        params = lm.init(jax.random.key(0))
        save_checkpoint("{tmp_path}", 3, params)

        # "failure": restore onto a 2x2 mesh (half the fleet)
        mesh4 = make_mesh((2, 2), ("data", "model"))
        lm4 = build_lm(cfg, mesh4)
        back = load_checkpoint("{tmp_path}", 3, params, mesh=mesh4,
                               specs=lm4.param_specs())
        # params live on mesh8's devices, back on mesh4's: compare on host
        ok = all(
            np.array_equal(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)))
        shardings = jax.tree.leaves(back)[0].sharding.mesh.shape
        print("RESULT:" + json.dumps(
            {{"equal": ok, "mesh": dict(shardings)}}))
    """)
    assert res["equal"]
    assert res["mesh"] == {"data": 2, "model": 2}
