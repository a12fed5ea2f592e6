"""Nominal work of focusing one scene, and the least time a chip needs
for it: the yardstick of every roofline share the benchmark reports.

The work is that of the transform, not of any implementation of it, so a
change of radix, of kernel count or of DFT algorithm leaves it alone:

- operations: 5 N log2 N per complex FFT line for the range FFT and IFFT
  (na lines of nr samples) and the azimuth FFT and IFFT (nr lines of na),
  plus 6 per sample (one complex multiply) for each of the three filter
  stages: range matched filter, RCMC phase, azimuth matched filter;
- bytes: one read of the complex64 echo and one write of the image,
  16 na nr.

A fusion that removes round trips through device memory raises the share;
work that the nominal count does not require never does.
"""
from __future__ import annotations

import json
import math
import pathlib

FILTER_STAGES = 3
OPS_PER_FILTER_SAMPLE = 6
BYTES_PER_SAMPLE = 16          # complex64 in, complex64 out

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def fft_ops(n: int) -> float:
    return 5.0 * n * math.log2(n)


def scene_ops(na: int, nr: int) -> float:
    return (2 * na * fft_ops(nr) + 2 * nr * fft_ops(na)
            + FILTER_STAGES * OPS_PER_FILTER_SAMPLE * na * nr)


def scene_bytes(na: int, nr: int) -> float:
    return float(BYTES_PER_SAMPLE * na * nr)


def peaks(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip; a kind missing from the table is an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path.name}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_seconds(na: int, nr: int, peak: dict) -> tuple[float, str]:
    """(least seconds for one scene, the bound that sets it)."""
    t_ops = scene_ops(na, nr) / peak["flops_per_s"]
    t_bytes = scene_bytes(na, nr) / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
