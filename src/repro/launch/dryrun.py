import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  * builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * jit-lowers the train/prefill/decode step with the schema-derived
    shardings against ShapeDtypeStruct inputs (no allocation),
  * compiles, prints memory_analysis() (proves fit) and cost_analysis()
    (FLOPs/bytes for §Roofline),
  * parses the optimized HLO for collective bytes (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute) -> roofline collective
    term,
  * writes one JSON record per cell to --out (results/dryrun/).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k [--multi-pod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import gzip
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, get_config, get_optimizer_name,
                           get_sharding_overrides)
from repro.launch import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, applicable, input_specs
from repro.models.model import abstract_params, ModelConfig
from repro.optim import get_optimizer, cosine_schedule
from repro.serve import engine
from repro.train.steps import make_train_step
from repro.launch import hloanalysis

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(hlo_type: str) -> int:
    """bytes of an HLO shape string like 'bf16[256,4096,3072]{2,1,0}'."""
    m = re.match(r"([a-z0-9]+)\[([\d,]*)\]", hlo_type)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the optimized HLO.
    Tuple shapes contribute each element."""
    out = {c: 0 for c in _COLLECTIVES}
    count = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # matches:  %name = TYPE all-gather(...)  /  ... = (T1, T2) all-reduce(
        m = re.match(r"%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s+([a-z\-]+)\(", stripped)
        if not m:
            continue
        op = m.group(2)
        if op.rstrip("-start") in _COLLECTIVES or op in [c + "-start" for c in _COLLECTIVES] or op in _COLLECTIVES:
            base = op[:-6] if op.endswith("-start") else op
            if base not in _COLLECTIVES:
                continue
            types = re.findall(r"[a-z0-9]+\[[\d,]*\]", m.group(1))
            total = sum(_shape_bytes(t) for t in types)
            out[base] += total
            count[base] += 1
    return {"bytes": out, "count": count,
            "total_bytes": int(sum(out.values()))}


def build_step(cfg: ModelConfig, shape, mesh, overrides):
    """Returns (jitted_fn, example_args_abstract) for the cell's step kind."""
    import dataclasses as _dc
    bax = sh.batch_axes(mesh, shape.global_batch)
    if bax is not None and not isinstance(bax, tuple):
        bax = (bax,)
    updates = dict(act_batch_axes=bax)
    if cfg.moe is not None and bax is not None:
        rules = sh.apply_overrides(sh.default_rules(mesh, cfg), overrides)
        gd = 1
        for a in bax:
            gd *= mesh.shape[a]
        gm = mesh.shape.get("model", 1)
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        if tokens % (gd * gm) == 0 and tokens // (gd * gm) >= cfg.moe.top_k:
            updates["moe_groups"] = (gd, gm)
            updates["moe_expert_sharded"] = rules.get("experts") == "model"
    cfg = _dc.replace(cfg, **updates)
    pspecs = sh.model_pspecs(mesh, cfg, overrides)
    params_abs = abstract_params(cfg)

    if shape.kind == "train":
        opt = get_optimizer(get_optimizer_name_from_cfg(cfg))
        step_fn = make_train_step(cfg, opt, cosine_schedule(3e-4, 100, 10000))
        opt_state_abs = jax.eval_shape(opt.init, params_abs)
        opt_specs = sh.opt_pspecs(pspecs, opt_state_abs)
        batch_abs = input_specs(cfg, shape)
        bspecs = sh.batch_specs(mesh, cfg, batch_abs)
        jitted = jax.jit(
            step_fn,
            in_shardings=sh.named(mesh, (pspecs, opt_specs, bspecs)),
            out_shardings=(*sh.named(mesh, (pspecs, opt_specs)), None),
            donate_argnums=(0, 1),
        )
        return jitted, (params_abs, opt_state_abs, batch_abs)

    if shape.kind == "prefill":
        batch_abs = input_specs(cfg, shape)
        bspecs = sh.batch_specs(mesh, cfg, batch_abs)
        cache_specs = sh.cache_pspecs(mesh, cfg, shape.global_batch,
                                      shape.seq_len)

        def fn(params, batch):
            return engine.prefill(params, cfg, tokens=batch.get("tokens"),
                                  embeds=batch.get("embeds"),
                                  positions=batch.get("positions"))

        jitted = jax.jit(fn, in_shardings=sh.named(mesh, (pspecs, bspecs)),
                         out_shardings=sh.named(
                             mesh, (sh.batch_pspec(mesh, shape.global_batch),
                                    cache_specs)))
        return jitted, (params_abs, batch_abs)

    # decode
    cache_abs = engine.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cache_specs = sh.cache_pspecs(mesh, cfg, shape.global_batch, shape.seq_len)
    tok_abs = input_specs(cfg, shape)["tokens"]
    bspec = P(sh.batch_axes(mesh, shape.global_batch))

    def fn(params, cache, tokens):
        logits, cache, _ = engine.decode_step(params, cfg, cache, tokens)
        return logits, cache

    jitted = jax.jit(fn, in_shardings=sh.named(mesh, (pspecs, cache_specs, bspec)),
                     out_shardings=sh.named(mesh, (bspec, cache_specs)),
                     donate_argnums=(1,))
    return jitted, (params_abs, cache_abs, tok_abs)


def get_optimizer_name_from_cfg(cfg) -> str:
    # adafactor for the 1T cell (see configs/kimi_k2_1t_a32b.py)
    return "adafactor" if cfg.name.startswith("kimi") else "adamw"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             cfg_override=None, save_hlo: bool = False,
             cfg_updates: dict | None = None) -> dict:
    cfg = cfg_override or get_config(arch)
    if cfg_updates:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_updates)
    shape = SHAPES[shape_name]
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "skipped", "reason": None,
    }
    if not applicable(cfg, shape):
        rec["reason"] = "long_500k skipped: pure full-attention arch (DESIGN.md §5)"
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = get_sharding_overrides(arch)
    with jax.set_mesh(mesh):
        jitted, args = build_step(cfg, shape, mesh, overrides)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)          # raw (body-once) counts
        executed = hloanalysis.analyze(hlo)   # trip-count-aware totals

    n_dev = mesh.devices.size
    rec.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        devices=n_dev,
        # raw cost_analysis (NOTE: while bodies counted once — see
        # hloanalysis; the "executed" block is the trip-count-aware truth)
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        executed=executed,
        collectives=coll,
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0),
        },
    )
    out_dir.mkdir(parents=True, exist_ok=True)
    fname = f"{arch.replace('.', '_')}__{shape_name}__{rec['mesh']}.json"
    (out_dir / fname).write_text(json.dumps(rec, indent=1))
    if save_hlo:
        with gzip.open(out_dir / (fname[:-5] + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override key=value (e.g. remat=dots)")
    args = ap.parse_args()
    cfg_updates = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        cfg_updates[k] = int(v) if v.isdigit() else v

    out_dir = Path(args.out)
    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                rec = run_cell(arch, shape, mp, out_dir,
                               save_hlo=args.save_hlo,
                               cfg_updates=cfg_updates or None)
                if rec["status"] == "ok":
                    m = rec["memory"]
                    ex = rec["executed"]
                    print(f"[ok]   {tag}: compile={rec['compile_s']}s "
                          f"exflops={ex['flops']:.3e} "
                          f"excoll={ex['collective_total_bytes']:.3e}B "
                          f"args={m['argument_bytes']/1e9:.2f}GB "
                          f"temp={m['temp_bytes']/1e9:.2f}GB", flush=True)
                else:
                    print(f"[skip] {tag}: {rec['reason']}", flush=True)
            except Exception as e:
                failures += 1
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
