"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production meshes, record memory/cost/collective analyses.

``main()`` asks for 512 placeholder host devices through XLA_FLAGS before
its first device query (jax fixes the device count when the backend first
initializes); importing this module changes nothing, so tests and benches
see the host's real device count.

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
  python -m repro.launch.dryrun --arch sar-rda-4k --mesh multi   # the paper's
                                                                 # own workload
"""
import argparse
import gzip
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch import roofline as rf
from repro.launch import sharding as shd
from repro.launch import specs, steps
from repro.launch.mesh import activation_rules, make_production_mesh
from repro.models import Model, use_mesh_rules
from repro.optim import adamw


def _flops_train(cfg, shape) -> float:
    """Analytic MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), D = tokens."""
    n = cfg.active_param_count()
    return 6.0 * n * shape.global_batch * shape.seq_len


def _flops_decode(cfg, shape) -> float:
    return 2.0 * cfg.active_param_count() * shape.global_batch


def _cell_lowered(cfg, shape, mesh, rules):
    """Build + lower the cell's step fn for `cfg`; returns (lowered, kind)."""
    model = Model(cfg)
    p_shape = specs.params_specs(model)
    p_shard = shd.param_shardings(p_shape, cfg, mesh, rules)
    p_sds = shd.attach(p_shape, p_shard)
    with use_mesh_rules(mesh, rules):
        if shape.kind == "train":
            opt_shape = jax.eval_shape(adamw.init, p_shape)
            opt_shard = {"mu": p_shard, "nu": p_shard,
                         "step": jax.sharding.NamedSharding(
                             mesh, jax.sharding.PartitionSpec())}
            opt_sds = shd.attach(opt_shape, opt_shard)
            b_shape = specs.batch_specs(cfg, shape, with_labels=True)
            b_sds = shd.attach(b_shape,
                               shd.batch_shardings(b_shape, mesh, rules))
            fn = steps.build_train_step(model)
            return jax.jit(fn, donate_argnums=(0, 1)).lower(
                p_sds, opt_sds, b_sds)
        if shape.kind == "prefill":
            b_shape = specs.batch_specs(cfg, shape, with_labels=False)
            b_sds = shd.attach(b_shape,
                               shd.batch_shardings(b_shape, mesh, rules))
            fn = steps.build_prefill(model, max_len=shape.seq_len)
            return jax.jit(fn).lower(p_sds, b_sds)
        c_shape = specs.cache_specs(model, shape)
        c_shard = shd.cache_shardings(c_shape, cfg, mesh, rules,
                                      shape.global_batch)
        c_sds = shd.attach(c_shape, c_shard)
        t_sds = specs.decode_token_specs(shape)
        fn = steps.build_decode(model)
        return jax.jit(fn, donate_argnums=(1,)).lower(p_sds, c_sds, t_sds)


def _hlo_flops(compiled) -> float:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost.get("flops", 0.0))


def scan_flops_correction(cfg, shape, mesh, rules) -> float:
    """XLA's cost_analysis counts a scan body ONCE regardless of trip count.
    Measure the per-period FLOPs by diffing two shallow *unrolled* lowerings
    at full width (1 vs 2 pattern periods) and add (trips - 1) x body."""
    import dataclasses as dc
    if not (cfg.scan_layers and cfg.n_periods > 1):
        return 0.0
    period = len(cfg.pattern)
    cfg1 = dc.replace(cfg, n_layers=period, scan_layers=False)
    cfg2 = dc.replace(cfg, n_layers=2 * period, scan_layers=False)
    f1 = _hlo_flops(_cell_lowered(cfg1, shape, mesh, rules).compile())
    f2 = _hlo_flops(_cell_lowered(cfg2, shape, mesh, rules).compile())
    body = max(f2 - f1, 0.0)
    return (cfg.n_periods - 1) * body


def _save_hlo(record: dict, compiled, out_dir, name: str):
    """Persist the post-SPMD HLO (gzipped) so roofline re-analysis never
    needs a recompile."""
    if not out_dir:
        return
    path = os.path.join(out_dir, name + ".hlo.gz")
    with gzip.open(path, "wt") as f:
        f.write(compiled.as_text())
    record["hlo"] = os.path.basename(path)


def lower_cell(arch: str, shape_name: str, mesh, out_dir=None, name=None,
               cached_correction=None) -> dict:
    """Lower + compile one cell; returns the record dict."""
    rules = activation_rules(mesh)
    n_dev = mesh.devices.size
    record = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(str(s) for s in mesh.devices.shape),
              "devices": int(n_dev)}

    if arch.startswith("sar-rda"):
        return _lower_sar(record, mesh, out_dir, name)
    shape = registry.SHAPES[shape_name]

    cfg = registry.get(arch)
    if shape.kind == "train":
        record["model_flops"] = _flops_train(cfg, shape)
    elif shape.kind == "prefill":
        record["model_flops"] = (2.0 * cfg.active_param_count()
                                 * shape.global_batch * shape.seq_len)
    else:
        record["model_flops"] = _flops_decode(cfg, shape)

    t0 = time.time()
    lowered = _cell_lowered(cfg, shape, mesh, rules)
    record["t_lower_s"] = round(time.time() - t0, 2)

    t0 = time.time()
    compiled = lowered.compile()
    record["t_compile_s"] = round(time.time() - t0, 2)

    mem = compiled.memory_analysis()
    record["memory"] = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes_per_device": int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
    }
    _save_hlo(record, compiled, out_dir, name or f"{arch}__{shape_name}")
    t0 = time.time()
    # cost_analysis is for the per-device SPMD program; the correction is
    # measured on the same partitioning, so it is per-device too.
    if cached_correction is not None:
        correction = cached_correction
    else:
        correction = scan_flops_correction(cfg, shape, mesh, rules)
    record["t_correction_s"] = round(time.time() - t0, 2)
    # model_flops is global 6ND; divide by chips to compare per-device
    roof = rf.from_compiled(compiled, n_dev,
                            record["model_flops"] / n_dev)
    roof.flops += correction
    record["scan_flops_correction_per_device"] = correction
    record["roofline"] = roof.to_dict()
    return record


def _lower_sar(record: dict, mesh, out_dir=None, name=None) -> dict:
    """The paper's own workload on the production mesh: distributed RDA
    (corner-turn schedule), all mesh axes pooled. `sar-rda-8k` is the
    paper's future-work target (8K x 8K real-time processing; its Table V
    competitors also run 8K scenes)."""
    from repro.core.sar import paper_scene
    from repro.core.sar.distributed import build_corner2

    n = 8192 if "8k" in record["arch"] else 4096
    cfg = paper_scene(na=n, nr=n)
    axes = tuple(mesh.axis_names)
    # interpret=True: Mosaic kernels cannot compile for the CPU backend; the
    # interpreted kernel lowers to equivalent HLO, so the collective schedule
    # and memory accounting (what this cell proves) are unchanged.
    run = build_corner2(cfg, mesh, axes=axes, interpret=True,
                        block=8, col_block=8)
    raw_sds = jax.ShapeDtypeStruct((cfg.na, cfg.nr), jnp.complex64)
    t0 = time.time()
    lowered = jax.jit(lambda x: run(x)).lower(raw_sds)
    record["t_lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    record["t_compile_s"] = round(time.time() - t0, 2)
    _save_hlo(record, compiled, out_dir, name or record["arch"])
    mem = compiled.memory_analysis()
    record["memory"] = {
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes_per_device": int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
    }
    # 2 FFT-ish passes * 5 N log N per point + filters
    import math
    n_pts = cfg.na * cfg.nr
    record["model_flops"] = (
        2 * 5 * n_pts * math.log2(cfg.nr) + 2 * 5 * n_pts * math.log2(cfg.na)
        + 3 * 6 * n_pts)
    roof = rf.from_compiled(compiled, mesh.devices.size,
                            record["model_flops"] / mesh.devices.size)
    record["roofline"] = roof.to_dict()
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--refresh", action="store_true",
                    help="recompute existing cells (reusing their cached "
                         "scan-flops corrections)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", ""))

    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    cells = []
    if args.all:
        cells = [(a, s) for a, s, skip in registry.cells() if skip is None]
        cells.append(("sar-rda-4k", "n/a"))
    else:
        assert args.arch, "--arch or --all required"
        if args.arch.startswith("sar"):
            cells = [(args.arch, "n/a")]
        else:
            cells = [(args.arch, args.shape or "train_4k")]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for multi in meshes[args.mesh]:
        mesh = make_production_mesh(multi_pod=multi)
        tag = "multi" if multi else "single"
        for arch, shape in cells:
            name = f"{arch}__{shape}__{tag}".replace("/", "_")
            path = os.path.join(args.out, name + ".json")
            cached = None
            if os.path.exists(path):
                old = json.load(open(path))
                if "roofline" in old and not args.refresh:
                    print(f"SKIP {name} (exists)")
                    continue
                cached = old.get("scan_flops_correction_per_device")
            try:
                rec = lower_cell(arch, shape, mesh, args.out, name, cached)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"OK   {name}: compile={rec['t_compile_s']}s "
                      f"mem={rec['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
                      f"t_comp={r['t_compute_s']*1e3:.2f}ms "
                      f"t_mem={r['t_memory_s']*1e3:.2f}ms "
                      f"t_coll={r['t_collective_s']*1e3:.2f}ms "
                      f"bound={r['bottleneck']}", flush=True)
            except Exception as e:
                failures += 1
                print(f"FAIL {name}: {e}", flush=True)
                traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
