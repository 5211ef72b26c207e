"""§Perf iteration driver for one (arch x shape) cell.

Re-lowers the cell with a named set of optimization knobs and prints the
three roofline terms — the measure step of the hypothesis -> change ->
measure -> validate loop.  Runs in-process (set XLA_FLAGS for 512 devices
before calling) or via the __main__ subprocess path.

Knobs (comma list in --variant):
  base            paper of record for the cell (what the dry-run ran)
  gradcomp        bf16 gradient compression before the all-reduce
  bf16params      cast 2D+ params to bf16 before use (bf16 FSDP gathers)
  nosp            disable sequence-parallel residual (ablation)
  adafactor       switch optimizer
"""
import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                               + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def lower_variant(arch: str, shape_name: str, variant: str, *,
                  multi_pod: bool = False, scan_layers: bool = False):
    from repro.configs.registry import batch_specs, get_config
    from repro.configs.shapes import SHAPES
    from repro.launch.dryrun import analyze_compiled
    from repro.launch.mesh import make_production_mesh
    from repro.models import model as M
    from repro.optim.optimizers import OptConfig
    from repro.sharding import hints
    from repro.sharding.rules import batch_spec as batch_pspec, param_shardings
    from repro.train.step import TrainConfig, init_train_state, make_train_step

    knobs = set(variant.split(","))
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = get_config(arch).replace(scan_layers=scan_layers)
    if "padheads" in knobs:
        # pad head counts up to the model-axis size so attention shards
        # (qwen1.5: 20 heads replicated 16-way -> 32 heads, 2/device)
        msize = 16
        pad = lambda h: -(-h // msize) * msize
        cfg = cfg.replace(n_heads=pad(cfg.n_heads),
                          n_kv_heads=pad(cfg.n_kv_heads))
    shape = SHAPES[shape_name]
    assert shape.kind == "train", "perf_cell drives train cells"

    tcfg = TrainConfig(
        opt=OptConfig(name="adafactor" if ("adafactor" in knobs
                                           or cfg.n_experts >= 64) else "adamw"),
        microbatches=1,
        grad_compression="gradcomp" in knobs,
        cast_params_bf16="bf16params" in knobs,
        logdet_reg=0.05 if "logdetreg" in knobs else 0.0,
    )
    hints.configure(cfg, None if "nosp" in knobs else mesh)
    if "nosp" in knobs:
        hints.configure(cfg.replace(family="ssm"), mesh)  # ssm => no seq-SP

    specs = batch_specs(cfg, shape.global_batch, shape.seq_len, kind="train")
    bspecs = batch_pspec(cfg, mesh, kind="train", batch=shape.global_batch)
    bshard = {k: NamedSharding(mesh, bspecs[k]) for k in specs}
    key = jax.random.PRNGKey(0)
    state_shapes = jax.eval_shape(lambda k: init_train_state(k, cfg, tcfg), key)
    state_shardings = {
        "params": param_shardings(state_shapes["params"], cfg, mesh),
        "opt": param_shardings(state_shapes["opt"], cfg, mesh),
        "step": NamedSharding(mesh, P()),
    }
    jitted = jax.jit(make_train_step(cfg, tcfg),
                     in_shardings=(state_shardings, bshard),
                     out_shardings=(state_shardings, None),
                     donate_argnums=(0,))
    t0 = time.time()
    with mesh:
        compiled = jitted.lower(state_shapes, specs).compile()
    n_active = M.count_params(cfg, active_only=True)
    rec = analyze_compiled(None, compiled, chips=chips, cfg=cfg, shape=shape,
                           n_active=n_active)
    rec["variant"] = variant
    rec["compile_s"] = round(time.time() - t0, 1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--scan", action="store_true")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    rec = lower_variant(args.arch, args.shape, args.variant,
                        scan_layers=args.scan)
    slim = {k: rec[k] for k in
            ("variant", "compute_s", "memory_s", "collective_s", "bottleneck",
             "hlo_flops_global", "useful_flops_frac", "wire_bytes_per_chip",
             "collective_bytes_by_op", "collective_counts", "compile_s")}
    slim["temp_gib"] = rec["memory"]["temp_bytes_per_device"] / 2 ** 30
    print(json.dumps(slim))


if __name__ == "__main__":
    main()
