"""Benchmark utilities: timing, CSV emission, JSON recording.

On a CPU host the Pallas kernels run in interpret mode, so wall-clock
numbers there time the interpreter, not a kernel; every row carries that
``interpret`` flag. Every bench prints `name,us_per_call,derived`
rows AND records them in-process so benchmarks/run.py can write
machine-readable BENCH_*.json artifacts (wall-ms + git SHA + backend) —
the cross-PR perf trajectory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time per call, seconds. Blocks on jax arrays."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


# ---------------------------------------------------------------------------
# CSV emission + in-process recording
# ---------------------------------------------------------------------------

_RECORDS: list[dict] = []
_RECORDS_MAX = 10_000   # library callers never drain; don't grow forever
_SECTION = [""]


def emit(name: str, seconds: float, derived: str = "",
         interpret: bool = None):
    """Record one benchmark row. ``interpret=True`` marks a row whose
    kernels ran in Pallas interpret mode (CPU emulation): its wall time
    measures the emulator, NOT the kernel — e.g. smoke runs at 128² show
    fused rows SLOWER than unfused, which misreads as a regression unless
    the flag is carried in the artifact. Comparisons (scripts/
    bench_compare.py) only diff rows whose interpret flags match."""
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)
    if len(_RECORDS) >= _RECORDS_MAX:
        del _RECORDS[: _RECORDS_MAX // 2]
    row = {
        "section": _SECTION[0],
        "name": name,
        "wall_ms": seconds * 1e3,
        "derived": derived,
    }
    if interpret is not None:
        row["interpret"] = bool(interpret)
    _RECORDS.append(row)


def pallas_interpreted() -> bool:
    """Whether Pallas rows in this process run in interpret mode: the
    kernels' own ``auto_interpret`` decision (interpreted on 'cpu',
    compiled on 'tpu', an error on any other backend)."""
    from repro.kernels.fft4step import auto_interpret
    return auto_interpret(None)


def header(title: str):
    print(f"# {title}", flush=True)
    _SECTION[0] = title


def take_records() -> list[dict]:
    """Drain and return everything emit()ed since the last call."""
    out = list(_RECORDS)
    _RECORDS.clear()
    return out


def git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


# BENCH_*.json artifact schema, version 2:
#   schema 1 (implicit) stamped a float `generated_unix`, which made
#   artifact diffs noisy (microsecond churn on every row-identical rerun)
#   and carried no version to validate against. Schema 2 stamps a
#   second-precision ISO-8601 UTC `generated_utc` plus an explicit
#   `schema: 2`, and benchmarks/run.py validates every artifact it writes
#   before CI uploads it (validate_bench_file).
#   Rows MAY carry an `interpret` bool (still schema 2 — the field is
#   optional): True marks wall times measured through the Pallas
#   interpreter (CPU emulation of the kernel, orders of magnitude off the
#   compiled ratio; fused rows can read SLOWER than unfused there).
#   Cross-run comparisons must only diff rows with matching flags.
BENCH_SCHEMA = 2
_REQUIRED_META = ("schema", "git_sha", "backend", "jax_version", "python",
                  "generated_utc", "rows")
_ISO_UTC_RE = r"^\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}Z$"


def utc_now_iso() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def write_bench_json(path: str, records: list[dict], **meta) -> None:
    """One BENCH_*.json artifact: rows + provenance (SHA, backend, host)."""
    doc = {
        "schema": BENCH_SCHEMA,
        "git_sha": git_sha(),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "python": sys.version.split()[0],
        "generated_utc": utc_now_iso(),
        **meta,
        "rows": records,
    }
    validate_bench_doc(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# wrote {path} ({len(records)} rows)", flush=True)


def validate_bench_doc(doc: dict) -> dict:
    """Assert `doc` is a well-formed schema-2 BENCH artifact. Returns the
    doc so callers can chain; raises ValueError with the first defect."""
    import re
    for key in _REQUIRED_META:
        if key not in doc:
            raise ValueError(f"BENCH doc missing required key {key!r}")
    if doc["schema"] != BENCH_SCHEMA:
        raise ValueError(f"BENCH schema {doc['schema']!r} != {BENCH_SCHEMA}")
    if not re.match(_ISO_UTC_RE, str(doc["generated_utc"])):
        raise ValueError(
            f"generated_utc {doc['generated_utc']!r} is not second-"
            "precision ISO-8601 UTC (YYYY-MM-DDTHH:MM:SSZ)")
    if not isinstance(doc["rows"], list):
        raise ValueError("rows must be a list")
    for i, row in enumerate(doc["rows"]):
        for key in ("section", "name", "wall_ms"):
            if key not in row:
                raise ValueError(f"rows[{i}] missing {key!r}")
        if not isinstance(row["wall_ms"], (int, float)):
            raise ValueError(f"rows[{i}].wall_ms is not a number")
        if "interpret" in row and not isinstance(row["interpret"], bool):
            raise ValueError(f"rows[{i}].interpret is not a bool")
    return doc


def validate_bench_file(path: str) -> dict:
    with open(path) as f:
        return validate_bench_doc(json.load(f))
