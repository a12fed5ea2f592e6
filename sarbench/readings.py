"""Readings of the compared number for the program and for its control.

    python3 sarbench/readings.py --workload <cell> --first-seed <n> \
        --seeds <count> --control <count> --seconds <s>

Runs the cell's whole loop (set-up, a short window at the cell's own load,
the comparison with the reference) once per seed, in one process on the
chip: first the program on ``--seeds`` seeds, then the control on
``--control`` further seeds. The control is the reference put in the
program's place with its FFTs computed in three bf16 passes
(``reference.transform_high``), the precision step below the f32 at
``Precision.HIGHEST`` the configurations state. Prints one JSON line per
run and a summary; limits in the configuration files are set from these
readings (largest program reading, smallest control reading). The
benchmark's own runs never run this.
"""
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control(config):
    """The reference in the program's place, its FFTs in three bf16
    passes."""
    from sarbench import reference, scene

    return reference.Reference(scene.scene_from_config(config), "high")


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from sarbench import harness, spec

    cell = spec.load_cell(args.workload)
    found = harness.accelerator(cell)
    if found is None:
        return 3
    devices, tag, peak = found
    runs = [("program", None)] * args.seeds + \
        [("control", control)] * args.control
    seen = {"program": [], "control": []}
    for i, (who, factory) in enumerate(runs):
        seed = args.first_seed + i
        out = harness.run_cell(cell, seed, args.seconds, False, tag=tag,
                               t_process=time.perf_counter(),
                               devices=devices, peak=peak, factory=factory)
        d = out["diagnostics"]
        value = out["compared"]["l2_rel"]["value"]
        seen[who].append(value)
        print(json.dumps({"who": who, "seed": seed, "l2_rel": value,
                          "by_band": d["l2_rel_by_range_band"],
                          "images": d["images_compared"],
                          "failed": out["failed"], "metrics": out["metrics"]}),
              flush=True)
    print(json.dumps({"workload": cell.name,
                      "program_max": max(seen["program"], default=None),
                      "control_min": min(seen["control"], default=None),
                      "program": seen["program"],
                      "control": seen["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
