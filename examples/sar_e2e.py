"""End-to-end driver (the paper's kind is imaging/inference): process a
batch of SAR scenes through every RDA variant, validate radar quality, and
print the paper's Tables II-IV analogs.

  PYTHONPATH=src python examples/sar_e2e.py                # 512^2, 3 scenes
  PYTHONPATH=src python examples/sar_e2e.py --n 4096 --scenes 1   # paper size
"""
import argparse
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.runtime import use_compile_cache
from repro.core.sar import (build_pipeline, metrics, paper_targets, simulate,
                            test_scene)
from repro.core.sar.geometry import paper_scene


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--scenes", type=int, default=3)
    args = ap.parse_args()

    cfg = paper_scene() if args.n == 4096 else test_scene(args.n)
    targets = paper_targets(cfg)

    print(f"scene {cfg.na}x{cfg.nr}: Kr={cfg.kr:.2e} Hz/s Ka={cfg.ka:.1f} Hz/s "
          f"res=({cfg.range_res:.2f} m, {cfg.azimuth_res:.2f} m) "
          f"noise={cfg.noise_db} dB")

    # batched requests: each scene has shifted targets + its own noise seed
    raws = []
    for s in range(args.scenes):
        c = dataclasses.replace(cfg, seed=1234 + s)
        raws.append(simulate(c, targets))
    print(f"simulated {args.scenes} scene(s)")

    variants = ["unfused", "fused", "fused_tfree", "fused3", "fused1",
                "omegak"]
    pipes = {v: build_pipeline(cfg, v) for v in variants}
    fns = {v: p.jitted() for v, p in pipes.items()}
    images, times = {}, {}
    for v in variants:
        jax.block_until_ready(fns[v](raws[0]))  # compile
        t0 = time.perf_counter()
        outs = [fns[v](r) for r in raws]
        jax.block_until_ready(outs)
        times[v] = (time.perf_counter() - t0) / args.scenes
        images[v] = np.asarray(outs[0])

    print("\n== Table II analog: end-to-end (per scene, CPU wall;"
          " on-device dispatch counts are the architecture story) ==")
    for v in variants:
        p = pipes[v]
        print(f"  {v:<12} {times[v]*1e3:9.1f} ms   dispatches={p.dispatches}"
              f"  hbm_roundtrips={p.hbm_roundtrips}"
              f"  speedup_model={pipes['unfused'].hbm_roundtrips/p.hbm_roundtrips:.1f}x(HBM)")

    print("\n== Table IV analog: quality (variant vs unfused) ==")
    for v in variants[1:]:
        c = metrics.compare_pipelines(images[v], images["unfused"], cfg,
                                      targets)
        print(f"  {v:<12} L2rel={c['l2_relative_error']:.3e} "
              f"maxabs={c['max_abs_error']:.3e} "
              f"snr_delta_max={max(c['snr_delta_db']):.4f} dB")

    print("\n== point targets (fused3 image) ==")
    for i, rep in enumerate(metrics.analyze_scene(images["fused3"], cfg,
                                                  targets)):
        print(f"  target {i}: ({rep.row},{rep.col}) snr={rep.snr_db:.1f} dB "
              f"pslr=({rep.pslr_range_db:.1f},{rep.pslr_azimuth_db:.1f}) dB "
              f"islr=({rep.islr_range_db:.1f},{rep.islr_azimuth_db:.1f}) dB")


if __name__ == "__main__":
    main()
