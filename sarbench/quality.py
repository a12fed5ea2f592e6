"""Image comparison arithmetic: the numbers that decide ``correct``, and
point-target diagnostics printed beside them.

The compared number is the relative L2 error ||image - reference|| /
||reference|| of a whole image, accumulated in float64
(``column_energies``, ``l2_from``); ``band_profile`` splits it over bands
of range columns as a diagnostic.
``target_reports`` is a copy of the paper's point-target arithmetic (peak
pixel, SNR against the noise outside guard windows), printed as a
diagnostic only: a lower-precision control reads the same peaks and SNR
to the hundredth of a dB, so it cannot separate a sound run from a
degraded one.
"""
from __future__ import annotations

import numpy as np

from sarbench.scene import Scene, Target


def column_energies(image: np.ndarray, reference: np.ndarray):
    """(error energy, reference energy) per range column, float64 sums,
    in one pass over row blocks."""
    num = np.zeros(image.shape[-1])
    den = np.zeros(image.shape[-1])
    step = max(1, image.shape[0] // 16)
    for lo in range(0, image.shape[0], step):
        a = np.asarray(image[lo:lo + step])
        b = np.asarray(reference[lo:lo + step])
        d = a - b
        num += np.sum(d.real.astype(np.float64) ** 2
                      + d.imag.astype(np.float64) ** 2, axis=0)
        den += np.sum(b.real.astype(np.float64) ** 2
                      + b.imag.astype(np.float64) ** 2, axis=0)
    return num, den


def l2_from(num: np.ndarray, den: np.ndarray, cols=slice(None)) -> float:
    return float(np.sqrt(num[cols].sum() / max(den[cols].sum(), 1e-300)))


def l2_relative_error(image: np.ndarray, reference: np.ndarray) -> float:
    return l2_from(*column_energies(image, reference))


def band_profile(num: np.ndarray, den: np.ndarray,
                 bands: int) -> np.ndarray:
    """Relative L2 error in each of ``bands`` equal bands of range
    columns."""
    w = num.size // bands
    return np.array([l2_from(num, den, slice(b * w, (b + 1) * w))
                     for b in range(bands)])


def expected_pixel(scene: Scene, tgt: Target) -> tuple[int, int]:
    """(row, col) of a focused target: closest approach in azimuth, the
    echo's start sample in range."""
    col = scene.nr / 2 + tgt.range_offset / scene.dr
    row = scene.na / 2 + tgt.azimuth_offset / scene.da
    return int(round(row)) % scene.na, int(round(col)) % scene.nr


def _find_peak(mag: np.ndarray, row: int, col: int, search: int = 8):
    na, nr = mag.shape
    rows = np.arange(row - search, row + search + 1) % na
    cols = np.arange(col - search, col + search + 1) % nr
    win = mag[np.ix_(rows, cols)]
    i, j = np.unravel_index(np.argmax(win), win.shape)
    return int(rows[i]), int(cols[j])


def noise_rms(image: np.ndarray, scene: Scene, targets: list[Target],
              guard: int = 64) -> float:
    mag = np.abs(image)
    mask = np.ones_like(mag, bool)
    for t in targets:
        r, c = expected_pixel(scene, t)
        rows = np.arange(r - guard, r + guard + 1) % scene.na
        cols = np.arange(c - guard, c + guard + 1) % scene.nr
        mask[np.ix_(rows, cols)] = False
    vals = mag[mask]
    return float(np.sqrt(np.mean(vals.astype(np.float64) ** 2))) \
        if vals.size else 0.0


def target_reports(image: np.ndarray, scene: Scene,
                   targets: list[Target]) -> list[tuple[int, int, float]]:
    """(peak row, peak col, SNR dB) for each target."""
    noise = noise_rms(image, scene, targets)
    mag = np.abs(image)
    out = []
    for t in targets:
        r, c = _find_peak(mag, *expected_pixel(scene, t))
        out.append((r, c, 20.0 * np.log10(float(mag[r, c]) /
                                          max(noise, 1e-30))))
    return out


def target_diff(image: np.ndarray, reference: np.ndarray, scene: Scene,
                targets: list[Target]) -> tuple[int, float]:
    """(targets whose peak pixel moved, largest SNR change in dB)."""
    a = target_reports(image, scene, targets)
    b = target_reports(reference, scene, targets)
    moved = sum((x[0], x[1]) != (y[0], y[1]) for x, y in zip(a, b))
    return moved, max(abs(x[2] - y[2]) for x, y in zip(a, b))
