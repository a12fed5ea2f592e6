"""Run one cell of the SAR focusing benchmark once, on the chip it finds.

    python3 sarbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration, its traffic mix
and its metrics are named in ``BENCHMARK.json``; their files are found by
name under ``sarbench/`` (see ``sarbench/README.md``, and ``spec.py`` for
the layout). The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics with ``--trace 0``, the per-layer ones with ``--trace 1``),
``device`` and, traced, ``breakdown``, then ``diagnostics`` and
``compared``: each number the correctness check compared, with its limit.
A traced run measures at most ``harness.TRACED_CALLS`` calls or
``--seconds``, whichever ends first, so its per-layer metrics and
``breakdown`` are those of at most that many calls; an untraced run
measures the whole ``--seconds``. Standard error ends with the
same numbers. Every line on standard error names the platform, the device
kind and the device count. A host where JAX finds no accelerator, or fewer
chips than the cell asks for, exits non-zero and prints no result.

JAX's persistent compilation cache is kept at ``.jax_cache`` in the
checkout, whatever the environment says, so that only the first run of a
cell in a checkout compiles.
"""
import os
import pathlib
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the script's own directory goes: its module names would shadow others
    sys.path[:1] = [str(ROOT), str(ROOT / "src")]
    from sarbench import harness

    return harness.main(t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
