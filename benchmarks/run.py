"""Benchmark harness — one module per paper table. CSV: name,us_per_call,derived.

  PYTHONPATH=src python -m benchmarks.run            # CPU-sized defaults
  PYTHONPATH=src python -m benchmarks.run --full     # the paper's 4096^2
  PYTHONPATH=src python -m benchmarks.run --only table_2
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI smoke + artifacts

Every run also writes machine-readable BENCH_fft.json / BENCH_rda.json /
BENCH_serve.json / BENCH_tuning.json / BENCH_sharded.json (wall-ms per
variant/size/batch + git SHA + backend; BENCH_serve includes the
seeded load-replay rows — goodput/deadline-miss/lane-occupancy of the
continuous-batching worker pool vs the single-flight baseline, gated
structurally by scripts/bench_compare.py --serve; BENCH_tuning records
guided-search wall time and predicted-vs-measured rank quality;
BENCH_sharded records the 8-device sharded-megakernel dispatch/turn
counts) so the perf trajectory is tracked across PRs; CI uploads them as
workflow artifacts.
"""
from __future__ import annotations

import argparse
import os

from benchmarks import (
    bench_compare,
    bench_fft,
    bench_quality,
    bench_rda,
    bench_serve,
    bench_tuning,
)
from benchmarks.common import take_records, validate_bench_file, \
    write_bench_json
from repro.runtime import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-size scenes (4096^2; slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized quick pass (small scenes, no tuning "
                         "sweeps) that still writes the BENCH_*.json "
                         "artifacts")
    ap.add_argument("--only", default=None,
                    help="table_1|table_2|table_3|table_4|table_5|table_6|"
                         "table_7|table_8")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    # table_8 shards across every device of this process; on a CPU host
    # that is 8 host devices, which XLA must be asked for before jax
    # starts (the flag touches only the host platform, never a TPU)
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))
    use_compile_cache()
    meta = dict(full=args.full, smoke=args.smoke)

    print("name,us_per_call,derived")
    want = lambda t: args.only is None or args.only == t
    written = []
    take_records()   # discard anything a previous in-process caller left
    if want("table_1"):
        bench_fft.run(full=args.full, smoke=args.smoke)
        write_bench_json("BENCH_fft.json", take_records(), **meta)
        written.append("BENCH_fft.json")
    if want("table_2") or want("table_3"):
        bench_rda.run(full=args.full, smoke=args.smoke)
        write_bench_json("BENCH_rda.json", take_records(), **meta)
        written.append("BENCH_rda.json")
    if want("table_4"):
        if args.smoke:
            print("# table_4 skipped in --smoke mode", flush=True)
        else:
            bench_quality.run(full=args.full)
    if want("table_5"):
        if args.smoke:
            print("# table_5 skipped in --smoke mode", flush=True)
        else:
            bench_compare.run(full=args.full)
    if want("table_6"):
        bench_serve.run(full=args.full, smoke=args.smoke)
        write_bench_json("BENCH_serve.json", take_records(), **meta)
        written.append("BENCH_serve.json")
    if want("table_7"):
        bench_tuning.run(full=args.full, smoke=args.smoke)
        write_bench_json("BENCH_tuning.json", take_records(), **meta)
        written.append("BENCH_tuning.json")
    if want("table_8"):
        bench_rda.run_sharded(full=args.full, smoke=args.smoke)
        write_bench_json("BENCH_sharded.json", take_records(), **meta)
        written.append("BENCH_sharded.json")
    if args.smoke:
        # CI uploads these as workflow artifacts — refuse to hand it a
        # malformed document (schema 2: versioned, ISO-8601 stamped).
        for path in written:
            validate_bench_file(path)
        print(f"# validated {len(written)} artifacts (schema 2)",
              flush=True)


if __name__ == "__main__":
    main()
