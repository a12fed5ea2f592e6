"""Serving quickstart: the async continuous-batching SAR focusing service.

Simulates a handful of clients firing concurrent focusing requests at a
FocusService — mixed precisions, some carrying deadlines (EDF-scheduled,
dropped with RequestCancelled when they can no longer be met) — then
prints the service's latency/batching/lane metrics. With more than one
host device (e.g. XLA_FLAGS=--xla_force_host_platform_\
device_count=8) pass --backend sharded to run the same requests through
the shard_map corner-turn backend.

  PYTHONPATH=src python examples/serve_sar.py --n 256 --requests 8
  PYTHONPATH=src python examples/serve_sar.py --backend sharded
"""
from __future__ import annotations

import argparse
import asyncio

import numpy as np

from repro.runtime import use_compile_cache
from repro.core.sar import paper_targets, simulate_cached
from repro.core.sar.geometry import test_scene
from repro.service import (
    FocusService,
    RequestCancelled,
    ServiceConfig,
    ShardedBackend,
    SnrGateViolation,
)


async def main(args) -> None:
    cfg = test_scene(args.n)
    raw = simulate_cached(cfg, paper_targets(cfg))

    backend = None
    if args.backend == "sharded":
        backend = ShardedBackend(schedule=args.schedule)
    svc = FocusService(
        ServiceConfig(
            variant=args.variant, backend=args.backend,
            max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
            device_budget_bytes=args.budget_bytes,
            lanes=args.lanes),
        backend=backend)

    print(f"warming {args.variant} for {cfg.na}x{cfg.nr} scenes ...")
    await svc.start(warm=[(cfg, args.variant, svc.default_precision)])

    async def client(i: int):
        # un-annotated requests take the default serving tier (bs16:
        # block-scaled f16, admitted only while its measured SNR
        # deviation clears the 0.1 dB gate — fails closed when the
        # quality harness is unavailable); every 4th request pins the
        # f32 verification path, which never consults the gate
        precision = "f32" if i % 4 == 3 else None
        # every other request carries a deadline: buckets flush
        # earliest-deadline-first, and a request still undispatched
        # past its deadline is dropped without costing a kernel launch
        deadline_ms = args.deadline_ms if i % 2 == 0 else None
        try:
            img = await svc.focus(raw * (1.0 + 0.1 * i), cfg,
                                  precision=precision,
                                  deadline_ms=deadline_ms)
        except SnrGateViolation as e:
            print(f"  request {i}: rejected by SNR gate ({e})")
            return None
        except RequestCancelled as e:
            print(f"  request {i}: dropped ({e})")
            return None
        print(f"  request {i}: focused, peak={float(np.abs(img).max()):.1f}"
              f" precision={precision or svc.default_precision or 'f32'}"
              + (f" deadline_ms={deadline_ms:g}" if deadline_ms else ""))
        return img

    await asyncio.gather(*[client(i) for i in range(args.requests)])
    await svc.stop()

    snap = svc.metrics.snapshot()
    print("\nservice metrics:")
    for k in ("completed", "rejected", "gate_rejected", "streamed",
              "cancelled", "deadline_met", "deadline_miss_rate",
              "latency_p50_ms", "latency_p99_ms", "throughput_rps",
              "goodput_rps", "mean_batch_size", "batch_size_hist",
              "batch_fill_hist", "lane_occupancy", "queue_depth_max"):
        print(f"  {k:18} {snap[k]}")
    if args.bench_json:
        svc.metrics.write_bench_json(args.bench_json)
        print(f"wrote {args.bench_json}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--variant", default="fused3")
    ap.add_argument("--backend", default="local",
                    choices=["local", "sharded"])
    ap.add_argument("--schedule", default="corner2",
                    choices=["corner2", "halo"])
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-delay-ms", type=float, default=10.0)
    ap.add_argument("--lanes", type=int, default=2,
                    help="worker-pool batch lanes (plus one stream lane)")
    ap.add_argument("--deadline-ms", type=float, default=30_000.0,
                    help="deadline attached to every other request")
    ap.add_argument("--budget-bytes", type=int, default=None,
                    help="device-memory budget; larger scenes stream")
    ap.add_argument("--bench-json", default=None,
                    help="write service metrics as a BENCH_*.json")
    use_compile_cache()
    asyncio.run(main(ap.parse_args()))
