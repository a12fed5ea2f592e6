"""Sharded megakernel demo: one 2048^2 SAR scene focused across 8
emulated devices, one staged megakernel dispatch per device per phase
group — the fused1 pipeline's in-kernel corner turns lowered to
all_to_all collectives (ROADMAP: paper scale beyond one device).

  PYTHONPATH=src python examples/sharded_scene.py            # 2048^2
  PYTHONPATH=src python examples/sharded_scene.py --n 1024   # quicker

The device-count flag must reach XLA before jax initializes, so it is
set here at import time; on real multi-device hardware drop the flag and
`make_sar_mesh()` picks up every visible device (multi-host capable:
devices sort by (process_index, id) so each host owns a contiguous block
of the sharded axis).
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse
import time

import numpy as np
import jax

from repro.runtime import use_compile_cache
from repro.core.sar import (build_pipeline, metrics, paper_targets,
                            simulate_cached, test_scene)
from repro.core.sar.distributed import make_sar_mesh
from repro.core.sar.geometry import paper_scene


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    args = ap.parse_args()

    # the rescaled CPU test scene aliases in azimuth past ~1900 lines
    # (fixed 400 Hz PRF); at 2048^2 and beyond the paper's own X-band
    # geometry is valid, so the demo runs the real regime there.
    cfg = paper_scene(args.n, args.n) if args.n >= 2048 else \
        test_scene(args.n)
    targets = paper_targets(cfg)
    raw = simulate_cached(cfg, targets)
    print(f"scene {cfg.na}x{cfg.nr} on {len(jax.devices())} "
          f"{jax.default_backend()} devices")

    # local single-device reference: the 3-dispatch fused3 pipeline the
    # sharded megakernel must reproduce (f32 bit-exact for the RDA family)
    ref_fn = build_pipeline(cfg, "fused3").jitted()
    jax.block_until_ready(ref_fn(raw))
    t0 = time.perf_counter()
    ref = np.asarray(ref_fn(raw))
    t_local = time.perf_counter() - t0

    # the sharded lowering: fused1's single mega step splits at its
    # in-kernel turn boundaries into per-device phase groups
    fn = build_pipeline(cfg, "fused1").lower_sharded(make_sar_mesh())
    jax.block_until_ready(fn(raw))
    t0 = time.perf_counter()
    img = np.asarray(fn(raw))
    t_shard = time.perf_counter() - t0

    print(f"\n== dispatch structure ({fn.devices} devices) ==")
    print(f"  dispatches per device: {fn.dispatches_per_device} "
          f"(one per phase group)")
    print(f"  collective corner turns: {fn.turns}")
    for u in fn.unit_info:
        print(f"    {u['name']:<16} stream_axis={u['stream_axis']} "
              f"kind={u['kind']} residency={u['residency']}")

    cmp = metrics.compare_pipelines(img, ref, cfg, targets)
    print(f"\n== parity vs local fused3 ==")
    print(f"  max |err|: {cmp['max_abs_error']:.3e}  "
          f"l2 rel: {cmp['l2_relative_error']:.3e}  "
          f"bit-identical: {np.array_equal(img, ref)}")
    for i, (snr, d) in enumerate(zip(cmp["snr_a_db"],
                                     cmp["snr_delta_db"])):
        print(f"  target {i}: snr={snr:.1f} dB (delta {d:.4f} dB)")
    print(f"\n  local fused3 {t_local*1e3:9.1f} ms | sharded fused1 "
          f"{t_shard*1e3:9.1f} ms (emulated devices; wall time measures "
          "the interpreter, the dispatch counts are the story)")


if __name__ == "__main__":
    main()
