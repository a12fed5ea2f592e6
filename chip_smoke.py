"""On-chip smoke test of the SAR focusing path.

    python chip_smoke.py            # one TPU chip: fused3 + fused1 + service
    python chip_smoke.py --chips 4  # the sharded fused1 path on four chips

One process drives everything (a chip belongs to the process that first
touches JAX). On one chip it simulates the paper's 4096 x 4096 scene
(Sec. V-A, five point targets, seeded), focuses it with the ``fused3``
and ``fused1`` pipelines as compiled Pallas kernels at f32 and at the
service's default tier for this device, checks every image against the
XLA ``unfused`` reference (same peak pixel and SNR within 0.1 dB for each
point target), and serves four requests through a ``FocusService`` that
may not fall back to another tier. ``--chips 4`` instead runs
``fused1`` lowered across a four-device mesh against single-device
``fused3``, with the same quality check.

Earlier lines report SNR deltas, compile times and warm wall times, each
naming the device. The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failure,
including a host without a TPU, exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

SNR_TOLERANCE_DB = 0.1


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def _check_image(name, img, ref, cfg, targets, metrics, tag) -> None:
    """Same peak pixel and SNR within SNR_TOLERANCE_DB for every target."""
    import numpy as np
    if img.shape != (cfg.na, cfg.nr) or not np.all(np.isfinite(img)):
        raise AssertionError(f"{name}: image shape {img.shape} or values "
                             "not finite")
    c = metrics.compare_pipelines(img, ref, cfg, targets)
    peaks = [(a.row, a.col) == (b.row, b.col)
             for a, b in zip(c["reports_a"], c["reports_b"])]
    deltas = c["snr_delta_db"]
    print(f"{tag} {name}: snr_delta_db="
          f"{[round(float(d), 4) for d in deltas]} peaks_match={peaks} "
          f"l2_rel={c['l2_relative_error']:.3e}", flush=True)
    if not all(peaks):
        raise AssertionError(f"{name}: a point target moved its peak pixel")
    if max(deltas) > SNR_TOLERANCE_DB:
        raise AssertionError(f"{name}: SNR delta {max(deltas):.4f} dB > "
                             f"{SNR_TOLERANCE_DB} dB")


def _compile(pipe, raw, tag, name):
    """AOT-compile the pipeline; the compiled HLO must hold a Mosaic
    kernel, or the run would not be a Pallas run at all."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(pipe.run).lower(raw).compile()
    secs = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    if kernels == 0:
        raise AssertionError(f"{name}: compiled HLO holds no "
                             "tpu_custom_call (no Pallas kernel ran)")
    print(f"{tag} {name}: compile {secs:.1f} s, {kernels} Mosaic kernel "
          "call(s) in the HLO", flush=True)
    return compiled


def _warm_seconds(fn, raw, iters: int = 3) -> float:
    import jax
    jax.block_until_ready(fn(raw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(raw))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def one_chip(cfg, targets, raw_host, tag) -> None:
    import jax
    import numpy as np
    from repro.core.sar import build_pipeline, metrics
    from repro.service import FocusService, ServiceConfig
    from repro.tuning.cost import device_spec

    raw = jax.device_put(raw_host)
    ref_pipe = build_pipeline(cfg, "unfused", rcmc_mode="fourier")
    ref = np.asarray(jax.jit(ref_pipe.run)(raw))
    tier = device_spec().serving_tier
    print(f"{tag} service default tier on this device: {tier}", flush=True)
    for variant in ("fused3", "fused1"):
        for precision in dict.fromkeys(("f32", tier)):
            name = f"{variant}/{precision}"
            pipe = build_pipeline(cfg, variant, precision=precision,
                                  interpret=False)
            compiled = _compile(pipe, raw, tag, name)
            img = np.asarray(compiled(raw))
            _check_image(name, img, ref, cfg, targets, metrics, tag)
            secs = _warm_seconds(compiled, raw)
            print(f"{tag} {name}: warm wall {secs * 1e3:.2f} ms per "
                  f"{cfg.na}x{cfg.nr} scene (block_until_ready, median "
                  "of 3)", flush=True)

    async def serve():
        svc = FocusService(ServiceConfig(variant="fused3", max_batch=2,
                                         tier_fallback=False))
        await svc.start(warm=[(cfg, "fused3", svc.default_precision)])
        try:
            imgs = await asyncio.gather(*[svc.focus(raw_host, cfg)
                                          for _ in range(4)])
        finally:
            await svc.stop()
        return svc, imgs

    t0 = time.perf_counter()
    svc, imgs = asyncio.run(serve())
    if dict(svc.backend.fallbacks):
        raise AssertionError(f"service fell back: {dict(svc.backend.fallbacks)}")
    for i, img in enumerate(imgs):
        _check_image(f"service request {i}", np.asarray(img), ref, cfg,
                     targets, metrics, tag)
    snap = svc.metrics.snapshot()
    print(f"{tag} service: 4 requests answered in "
          f"{time.perf_counter() - t0:.1f} s including warm-up; "
          f"batch_size_hist={snap['batch_size_hist']} fallbacks=none",
          flush=True)


def four_chips(cfg, targets, raw_host, tag) -> None:
    import jax
    import numpy as np
    from repro.core.sar import build_pipeline, metrics
    from repro.core.sar.distributed import make_sar_mesh

    raw = jax.device_put(raw_host, jax.devices()[0])
    single = build_pipeline(cfg, "fused3", interpret=False)
    compiled = _compile(single, raw, tag, "fused3 single-device")
    ref = np.asarray(compiled(raw))
    secs = _warm_seconds(compiled, raw)
    print(f"{tag} fused3 single-device: warm wall {secs * 1e3:.2f} ms per "
          "scene", flush=True)

    t0 = time.perf_counter()
    run = build_pipeline(cfg, "fused1", interpret=False).lower_sharded(
        make_sar_mesh())
    out = jax.block_until_ready(run(jax.device_put(raw_host)))
    print(f"{tag} fused1 sharded: first call (compile + run) "
          f"{time.perf_counter() - t0:.1f} s, devices={run.devices}, "
          f"dispatches_per_device={run.dispatches_per_device}, "
          f"turns={run.turns}", flush=True)
    if run.devices != 4:
        raise AssertionError(f"sharded run spans {run.devices} devices")
    if len(out.sharding.device_set) != 4:
        raise AssertionError("sharded image lives on "
                             f"{len(out.sharding.device_set)} devices")
    _check_image("fused1 sharded vs fused3", np.asarray(out), ref, cfg,
                 targets, metrics, tag)
    secs = _warm_seconds(run, jax.device_put(raw_host))
    print(f"{tag} fused1 sharded: warm wall {secs * 1e3:.2f} ms per scene",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: single-chip paths and the service; 4: only "
                         "the sharded fused1 path and its reference")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the simulated scene's noise")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro.runtime import use_compile_cache
    except ImportError as e:
        return _fail(f"the repro package is not beside this script ({e})")
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        # the scene is simulated on the host CPU device (float64 phase
        # math); the accelerator named first stays the default backend
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"JAX found no TPU (default device {dev.platform} "
                     f"{dev.device_kind!r})")
    if len(devices) != args.chips:
        return _fail(f"--chips {args.chips} but JAX sees {len(devices)} "
                     "TPU devices")
    print(f"[{dev.device_kind} x{len(devices)}] compile cache: "
          f"{use_compile_cache()}", flush=True)
    tag = f"[{dev.device_kind} x{len(devices)}]"

    from repro.core.sar import paper_scene, paper_targets, simulate
    cfg = dataclasses.replace(paper_scene(), seed=args.seed)
    targets = paper_targets(cfg)
    t0 = time.perf_counter()
    raw_host = simulate(cfg, targets)       # host CPU: float64 phase math
    print(f"{tag} simulated {cfg.na}x{cfg.nr} scene, {len(targets)} point "
          f"targets, seed {args.seed}, in {time.perf_counter() - t0:.1f} s "
          "on the host CPU", flush=True)
    try:
        (one_chip if args.chips == 1 else four_chips)(
            cfg, targets, raw_host, tag)
    except Exception as e:                  # noqa: BLE001 — reported, exit 1
        import traceback
        traceback.print_exc()
        return _fail(f"{type(e).__name__}: {e}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
