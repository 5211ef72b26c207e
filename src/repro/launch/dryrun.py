"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THIS FILE MUST SET XLA_FLAGS BEFORE ANY OTHER IMPORT — jax locks the device
count on first init.  512 placeholder host devices cover both the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402  (the two lines above are load-bearing)
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCHS, batch_specs, get_config, skip_shapes
from repro.configs.shapes import SHAPES
from repro.launch.hlo_analysis import HW, collective_bytes, roofline
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.common import ModelConfig
from repro.optim.optimizers import OptConfig
from repro.sharding import hints
from repro.sharding.rules import (
    batch_spec as batch_pspec, cache_shardings, make_rules, param_shardings,
)
from repro.train.step import TrainConfig, init_train_state, make_train_step


def _tcfg_for(cfg: ModelConfig, *, cost_pass: bool = False) -> TrainConfig:
    # 400B MoE: AdamW's 8 bytes/param of moments cannot fit 256 chips;
    # Adafactor's factored second moment can (DESIGN.md memory budget);
    # 8-way microbatching + bf16 accumulation bound the activation slab.
    import jax.numpy as jnp
    big = cfg.n_experts >= 64
    return TrainConfig(
        opt=OptConfig(name="adafactor" if big else "adamw"),
        microbatches=1 if cost_pass else (16 if big else 1),
        accum_dtype=jnp.bfloat16 if big else jnp.float32,
    )


def _serving_cfg(cfg: ModelConfig) -> ModelConfig:
    # 32k prefill with materialized (T x T) logits would be ~4 TB/device;
    # serving paths always use the chunked (flash-style) attention.
    return cfg.replace(attn_impl="chunked", remat=False)


def lower_cell(arch: str, shape_name: str, mesh, *, smoke: bool = False,
               scan_layers: bool = True):
    """Build + lower one cell; returns (lowered, n_model_params, cfg).

    scan_layers=True  -> deployment form: lax.scan over layers (fast compile,
                         realistic memory_analysis).
    scan_layers=False -> unrolled: cost_analysis counts while bodies ONCE, so
                         the roofline pass lowers unrolled for exact per-step
                         FLOPs / bytes / collective traffic.
    """
    cfg = get_config(arch, smoke=smoke)
    cfg = cfg.replace(scan_layers=scan_layers)
    shape = SHAPES[shape_name]
    specs = batch_specs(cfg, shape.global_batch, shape.seq_len, kind=shape.kind)
    bspecs = batch_pspec(cfg, mesh, kind=shape.kind, batch=shape.global_batch)
    bshard = {k: NamedSharding(mesh, bspecs[k]) for k in specs}

    # long_500k (global_batch < data axes): the KV cache is sequence-sharded
    # and decode must use the masked-write path (see sharding/hints.py)
    data_axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    dsize = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
    masked = shape.kind == "decode" and shape.global_batch % dsize != 0
    hints.configure(cfg, mesh, kv_masked_write=masked)
    key = jax.random.PRNGKey(0)

    if shape.kind == "train":
        # cost pass (unrolled): microbatches=1 — the accumulation loop is a
        # scan (counted once by cost_analysis) and per-step FLOPs/collective
        # totals are microbatch-invariant; memory truth comes from the scan
        # pass which uses the real microbatched config.
        tcfg = _tcfg_for(cfg, cost_pass=not scan_layers)
        state_shapes = jax.eval_shape(
            lambda k: init_train_state(k, cfg, tcfg), key)
        state_shardings = {
            "params": param_shardings(state_shapes["params"], cfg, mesh),
            "opt": param_shardings(state_shapes["opt"], cfg, mesh),
            "step": NamedSharding(mesh, P()),
        }
        step_fn = make_train_step(cfg, tcfg)
        jitted = jax.jit(step_fn,
                         in_shardings=(state_shardings, bshard),
                         out_shardings=(state_shardings, None),
                         donate_argnums=(0,))
        with mesh:
            lowered = jitted.lower(state_shapes, specs)

    elif shape.kind == "prefill":
        scfg = _serving_cfg(cfg)
        params_shapes = jax.eval_shape(lambda k: M.init_model(k, scfg), key)
        pshard = param_shardings(params_shapes, scfg, mesh)
        cspec = M.cache_specs(scfg, shape.global_batch, shape.seq_len)
        cshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              cache_shardings(cspec, scfg, mesh),
                              is_leaf=lambda x: isinstance(x, P))

        def prefill_fn(params, batch):
            return M.prefill(params, batch, scfg, shape.seq_len)

        jitted = jax.jit(prefill_fn, in_shardings=(pshard, bshard),
                         out_shardings=(None, cshard))
        with mesh:
            lowered = jitted.lower(params_shapes, specs)

    elif shape.kind == "decode":
        scfg = _serving_cfg(cfg)
        params_shapes = jax.eval_shape(lambda k: M.init_model(k, scfg), key)
        pshard = param_shardings(params_shapes, scfg, mesh)
        cspec = M.cache_specs(scfg, shape.global_batch, shape.seq_len)
        cshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              cache_shardings(cspec, scfg, mesh),
                              is_leaf=lambda x: isinstance(x, P))
        pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
        tok_spec = specs.pop("tokens")
        extras = specs or None
        eshard = {k: bshard[k] for k in (extras or {})} or None

        def decode_fn(params, tokens, caches, pos, extras_):
            return M.decode_step(params, tokens, caches, pos, scfg,
                                 batch_extras=extras_)

        jitted = jax.jit(
            decode_fn,
            in_shardings=(pshard, bshard["tokens"], cshard,
                          NamedSharding(mesh, P()), eshard),
            out_shardings=(None, cshard),
            donate_argnums=(2,))
        with mesh:
            lowered = jitted.lower(params_shapes, tok_spec, cspec,
                                   pos_spec, extras)
    else:
        raise ValueError(shape.kind)

    n_active = M.count_params(cfg, active_only=True)
    return lowered, n_active, cfg


def analyze_compiled(lowered, compiled, *, chips: int, cfg, shape, n_active):
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    stats = collective_bytes(compiled.as_text())
    n_tok = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    model_fl = 2 * n_active * n_tok * (3 if shape.kind == "train" else 1)
    # SSD/conv inner scans stay rolled even with unrolled layers (8k chunk
    # trips at 500k) -> cost_analysis undercounts those cells; take the max
    # of compiled and analytic FLOPs for the compute term (documented).
    flops_global = max(flops_dev * chips, float(model_fl))
    terms = roofline(flops=flops_global, hbm_bytes=bytes_dev * chips,
                     wire_bytes_per_chip=stats.wire_bytes, chips=chips)
    rec = {
        "arch": cfg.name, "shape": shape.name, "kind": shape.kind,
        "chips": chips,
        "hlo_flops_global": flops_dev * chips,
        "hlo_bytes_global": bytes_dev * chips,
        "wire_bytes_per_chip": stats.wire_bytes,
        "collective_counts": stats.counts,
        "collective_bytes_by_op": stats.by_op,
        "model_flops": model_fl,
        "useful_flops_frac": model_fl / max(flops_dev * chips, 1.0),
        **{k: terms[k] for k in
           ("compute_s", "memory_s", "collective_s", "bottleneck",
            "step_s_lower_bound")},
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
        },
    }
    return rec


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, smoke: bool = False,
             verbose: bool = True, fast: bool = False):
    """Two lowerings per cell: scan (memory truth) + unrolled (cost truth)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))

    # pass 1: deployment form — the compile that must succeed + memory proof
    t0 = time.time()
    lowered, n_active, cfg = lower_cell(arch, shape_name, mesh, smoke=smoke,
                                        scan_layers=True)
    compiled = lowered.compile()
    t1 = time.time()
    rec = analyze_compiled(lowered, compiled, chips=chips, cfg=cfg,
                           shape=SHAPES[shape_name], n_active=n_active)
    rec["scan_compile_s"] = round(t1 - t0, 1)

    # pass 2: unrolled — exact per-step FLOPs / bytes / collectives
    if not fast:
        t2 = time.time()
        lowered_u, _, _ = lower_cell(arch, shape_name, mesh, smoke=smoke,
                                     scan_layers=False)
        compiled_u = lowered_u.compile()
        t3 = time.time()
        rec_u = analyze_compiled(lowered_u, compiled_u, chips=chips, cfg=cfg,
                                 shape=SHAPES[shape_name], n_active=n_active)
        rec_u["memory_unrolled_temp_bytes"] = \
            rec_u["memory"]["temp_bytes_per_device"]
        rec_u["memory"] = rec["memory"]   # memory truth: deployment form
        rec_u["scan_compile_s"] = round(t1 - t0, 1)
        rec_u["unrolled_compile_s"] = round(t3 - t2, 1)
        rec = rec_u

    rec["mesh"] = "2x16x16" if multi_pod else "16x16"
    if verbose:
        m = rec["memory"]
        print(f"[{rec['mesh']}] {arch} x {shape_name}: "
              f"args={m['argument_bytes_per_device']/2**30:.2f}GiB "
              f"temp={m['temp_bytes_per_device']/2**30:.2f}GiB "
              f"flops/dev={rec['hlo_flops_global']/chips:.3e} "
              f"wire/dev={rec['wire_bytes_per_chip']:.3e}B "
              f"bottleneck={rec['bottleneck']} "
              f"(compiles {rec.get('scan_compile_s')}s scan"
              + (f", {rec.get('unrolled_compile_s')}s unrolled)" if not fast
                 else ")"))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (CI sanity)")
    ap.add_argument("--fast", action="store_true",
                    help="skip the unrolled cost pass (scan costs only)")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    archs = ARCHS if args.arch == "all" else [args.arch]
    shape_names = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    out_path = Path(args.out)
    done = set()
    if args.skip_existing and out_path.exists():
        for line in out_path.read_text().splitlines():
            try:
                r = json.loads(line)
                done.add((r["arch"], r["shape"], r["mesh"]))
            except Exception:
                pass

    failures = []
    with out_path.open("a") as f:
        for arch in archs:
            skips = skip_shapes(arch)
            for shape_name in shape_names:
                for multi_pod in meshes:
                    mesh_name = "2x16x16" if multi_pod else "16x16"
                    cfg_name = get_config(arch).name
                    if (cfg_name, shape_name, mesh_name) in done:
                        continue
                    if shape_name in skips:
                        rec = {"arch": cfg_name, "shape": shape_name,
                               "mesh": mesh_name, "skipped": True,
                               "reason": "full-attention arch: long_500k "
                                         "needs sub-quadratic attention"}
                        print(f"[{mesh_name}] {arch} x {shape_name}: SKIP")
                        f.write(json.dumps(rec) + "\n")
                        f.flush()
                        continue
                    try:
                        # unrolled cost pass: single-pod only (the roofline
                        # table is single-pod; multi-pod proves sharding).
                        rec = run_cell(arch, shape_name, multi_pod=multi_pod,
                                       smoke=args.smoke,
                                       fast=(multi_pod or args.fast))
                        f.write(json.dumps(rec) + "\n")
                        f.flush()
                    except Exception as e:  # noqa: BLE001 — report & continue
                        failures.append((arch, shape_name, mesh_name, repr(e)))
                        traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for fll in failures:
            print("  ", *fll[:3], fll[3][:200])
        sys.exit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()
