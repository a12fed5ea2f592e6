"""Batched multi-scene SAR focusing — the production serving shape.

A constellation downlink delivers many scenes with identical acquisition
geometry; focusing them one at a time leaves the accelerator idle between
dispatches. This example stacks B raw scenes into a (B, na, nr) batch and
runs the fused RDA ONCE — every stage is a single Pallas dispatch whose
grid spans B x line-blocks, so dispatch overhead and the DFT-constant loads
amortize across the batch — then verifies the batched images are bit-exact
against per-scene focusing and reports the per-scene latency win.

  PYTHONPATH=src python examples/batch_scenes.py                 # 256^2, B=4
  PYTHONPATH=src python examples/batch_scenes.py --n 512 --batch 8
  PYTHONPATH=src python examples/batch_scenes.py --variant fused_tfree
"""
import argparse
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.runtime import use_compile_cache
from repro.core.sar import build_pipeline, metrics, paper_targets, simulate
from repro.core.sar.geometry import test_scene


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--variant", default="fused3",
                    choices=["unfused", "fused", "fused_tfree", "fused3",
                             "omegak", "csa_fused"])
    args = ap.parse_args()

    cfg = test_scene(args.n)
    targets = paper_targets(cfg)

    # B scenes, same geometry, different noise realizations
    print(f"simulating {args.batch} scenes of {cfg.na}x{cfg.nr} ...")
    scenes = [simulate(dataclasses.replace(cfg, seed=s), targets)
              for s in range(args.batch)]
    raw_batch = jnp.stack(scenes)                      # (B, na, nr)

    pipe = build_pipeline(cfg, args.variant)
    focus = pipe.jitted()

    # per-scene reference (B separate dispatch sequences)
    one = jax.jit(pipe.run)
    imgs_seq = [one(s) for s in scenes]
    jax.block_until_ready(imgs_seq)
    t0 = time.perf_counter()
    imgs_seq = [one(s) for s in scenes]
    jax.block_until_ready(imgs_seq)
    t_seq = time.perf_counter() - t0

    # batched: one dispatch sequence for all B scenes
    imgs_b = focus(raw_batch)
    jax.block_until_ready(imgs_b)
    t0 = time.perf_counter()
    imgs_b = focus(raw_batch)
    jax.block_until_ready(imgs_b)
    t_batch = time.perf_counter() - t0

    imgs_b = np.asarray(imgs_b)                      # the host copy
    err = float(np.max(np.abs(imgs_b - np.asarray(jnp.stack(imgs_seq)))))
    print(f"batched vs per-scene max abs diff: {err:.3e}")
    assert err == 0.0, f"batched focusing diverged from per-scene: {err}"

    for i in range(args.batch):
        reps = metrics.analyze_scene(imgs_b[i], cfg, targets)
        worst = min(r.snr_db for r in reps)
        print(f"scene {i}: worst target SNR {worst:.1f} dB")

    print(f"\nvariant={args.variant}  B={args.batch}")
    print(f"  per-scene (sequential): {t_seq / args.batch * 1e3:8.1f} ms")
    print(f"  per-scene (batched):    {t_batch / args.batch * 1e3:8.1f} ms")
    print(f"  amortization:           {t_seq / t_batch:8.2f}x")


if __name__ == "__main__":
    main()
