"""Distribution tests.

* sharded-vs-single-device numerical equivalence on an 8-device CPU mesh
  (subprocess: device count must be set before jax initializes),
* dry-run cell smoke on a small mesh (lower+compile+analyze in-process is
  not possible after jax init, so these also go through subprocesses),
* sharding-rule unit checks that don't need devices.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
ENV = dict(os.environ, PYTHONPATH=str(REPO / "src"),
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           JAX_PLATFORMS="cpu")


def run_py(code: str, timeout=480):
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=ENV,
                          timeout=timeout, cwd=REPO)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    r = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        import dataclasses
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.launch import sharding as sh
        from repro.launch.mesh import make_mesh
        from repro.models.model import init_params
        from repro.optim import get_optimizer, cosine_schedule
        from repro.train.steps import make_train_step

        cfg = get_smoke_config("llama3.2-3b")
        params = init_params(cfg, jax.random.key(0))
        opt = get_optimizer("adamw")
        state = opt.init(params)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
        }
        step = make_train_step(cfg, opt, cosine_schedule(1e-3, 10, 100))

        # single device reference
        p1, s1, m1 = jax.jit(step)(params, state, batch)

        # 2x4 mesh, sharded
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg2 = dataclasses.replace(cfg, act_batch_axes=("data",))
        step2 = make_train_step(cfg2, opt, cosine_schedule(1e-3, 10, 100))
        with jax.set_mesh(mesh):
            pspecs = sh.model_pspecs(mesh, cfg2)
            ospecs = sh.opt_pspecs(pspecs, state)
            bspecs = sh.batch_specs(mesh, cfg2, batch)
            jitted = jax.jit(
                step2,
                in_shardings=sh.named(mesh, (pspecs, ospecs, bspecs)),
                out_shardings=(*sh.named(mesh, (pspecs, ospecs)), None))
            p2, s2, m2 = jitted(params, state, batch)

        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2, \\
            (float(m1["loss"]), float(m2["loss"]))
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        worst = max(jax.tree.leaves(d))
        assert worst < 5e-2, worst
        print("OK", float(m1["loss"]), worst)
    """)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_dryrun_cell_compiles_on_small_mesh():
    """The dry-run machinery end-to-end on a 2x4 mesh with a smoke config
    (the production 16x16/2x16x16 sweep runs via launch.dryrun --all)."""
    r = run_py("""
        import jax, dataclasses
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.launch import sharding as sh, hloanalysis
        from repro.launch.shapes import ShapeSpec
        from repro.launch.dryrun import build_step

        for arch in ("mixtral-8x22b", "rwkv6-3b", "zamba2-2.7b"):
            cfg = get_smoke_config(arch)
            mesh = make_mesh((2, 4), ("data", "model"))
            shape = ShapeSpec("t", 64, 8, "train")
            with jax.set_mesh(mesh):
                jitted, args = build_step(cfg, shape, mesh, {})
                compiled = jitted.lower(*args).compile()
                res = hloanalysis.analyze(compiled.as_text())
                assert res["flops"] > 0
            print("OK", arch, f"{res['flops']:.2e}")
    """)
    assert r.stdout.count("OK") == 3, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


@pytest.mark.slow
def test_serve_decode_compiles_sharded():
    r = run_py("""
        import jax
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.launch.shapes import ShapeSpec
        from repro.launch.dryrun import build_step

        cfg = get_smoke_config("internlm2-1.8b")
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeSpec("d", 128, 8, "decode")
        with jax.set_mesh(mesh):
            jitted, args = build_step(cfg, shape, mesh, {})
            compiled = jitted.lower(*args).compile()
        print("OK")
    """)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


# ------------------------------------------------------- rule units (no devices)
def test_sharding_rules_divisibility():
    from repro.configs import get_config
    from repro.models.model import param_pspecs

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    cfg = get_config("mixtral-8x22b")
    from repro.launch.sharding import default_rules
    rules = default_rules(FakeMesh(), cfg)
    assert rules["experts"] is None          # 8 experts don't divide 16
    assert rules["expert_mlp"] == "model"    # TP inside experts instead

    cfg2 = get_config("kimi-k2-1t-a32b")
    rules2 = default_rules(FakeMesh(), cfg2)
    assert rules2["experts"] == "model"      # 384 divides 16 -> EP
    assert rules2["kv_heads"] is None        # 8 kv heads don't divide 16

    # every pspec entry only references real axes
    specs = param_pspecs(cfg2, rules2)
    import jax
    from jax.sharding import PartitionSpec as P
    for leaf in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        for ax in leaf:
            assert ax in (None, "data", "model", "pod"), leaf


def test_batch_axes_divisibility():
    from repro.launch.sharding import batch_axes

    class M:
        shape = {"pod": 2, "data": 16, "model": 16}

    assert batch_axes(M(), 256) == ("pod", "data")
    assert batch_axes(M(), 16) == "data"
    assert batch_axes(M(), 1) is None


@pytest.mark.slow
def test_shard_map_moe_matches_reference():
    """The expert-parallel shard_map dispatch must be numerically identical
    to the single-program sort/scatter path (same capacity-per-group)."""
    r = run_py("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.launch import sharding as sh
        from repro.models.model import init_params, forward

        base = get_smoke_config("kimi-k2-1t-a32b")
        params = init_params(base, jax.random.key(0))
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, base.vocab_size, (4, 16)))

        # reference: single program, but with per-group capacity semantics:
        # emulate by running the sharded config on a (2,4) mesh and comparing
        # against the same grouped math traced WITHOUT the mesh is not
        # possible; instead check mesh-run vs mesh-run with expert_sharded
        # False (pure GSPMD) — dispatch math must agree where no tokens drop.
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg_ep = dataclasses.replace(
            base, act_batch_axes=("data",), moe_groups=(2, 4),
            moe_expert_sharded=True,
            moe=dataclasses.replace(base.moe, capacity_factor=8.0))
        cfg_ref = dataclasses.replace(
            base, act_batch_axes=("data",),
            moe=dataclasses.replace(base.moe, capacity_factor=8.0))
        with jax.set_mesh(mesh):
            pspecs = sh.model_pspecs(mesh, cfg_ep)
            bspec = sh.batch_specs(mesh, cfg_ep, {"tokens": toks})["tokens"]
            shardings = sh.named(mesh, (pspecs, bspec))
            f_ep = jax.jit(lambda p, t: forward(p, cfg_ep, tokens=t)[0],
                           in_shardings=shardings)
            f_ref = jax.jit(lambda p, t: forward(p, cfg_ref, tokens=t)[0],
                            in_shardings=shardings)
            h_ep = np.asarray(f_ep(params, toks), np.float32)
            h_ref = np.asarray(f_ref(params, toks), np.float32)
        err = np.abs(h_ep - h_ref).max()
        # bf16 activations: one ulp at |h|~2 is 2^-5 = 0.03125, and the two
        # dispatch formulations sum expert outputs in different orders
        assert err <= 2 ** -4, err
        print("OK", err)
    """)
    assert "OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
