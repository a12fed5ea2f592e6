"""The plain reference: textbook range-Doppler focusing with ``jnp.fft``.

Four steps, each on the whole scene (Cumming & Wong, ch. 6):

1. range compression: range FFT, times the chirp's matched filter, IFFT;
2. azimuth FFT into the range-Doppler domain;
3. range cell migration correction as a Fourier shift of each Doppler row
   by ``r0 (1/D(f_a) - 1) / dr`` samples (range FFT, phase ramp, IFFT);
4. azimuth compression: times the exact hyperbolic azimuth filter
   ``exp(i 4 pi fc r0(r) (D(f_a) - 1) / c)``, azimuth IFFT.

It imports nothing of the program under test. Its filters are built here,
in float64 on the host, and passed to one small jitted function as
arguments, so its executable holds no scene-sized constants and is found
in the compile cache by every run after the first.

``transform_high`` is the control: the same focusing with every FFT done
as a four-step DFT whose matrix products run in three bf16 passes with f32
accumulation -- the arithmetic of ``jax.lax.Precision.HIGH`` on a TPU,
spelled out so that it rounds the same way on every backend. The program
states f32 DFTs at ``Precision.HIGHEST``; ``HIGH`` is the step below it.
"""
from __future__ import annotations

import concurrent.futures
import functools
import os

import numpy as np

from sarbench.scene import C, Scene


# ---------------------------------------------------------------------------
# Filters (float64 on the host)
# ---------------------------------------------------------------------------

def range_filter(scene: Scene) -> np.ndarray:
    """conj(FFT(chirp replica at fast-time offset 0)), (nr,) complex64."""
    n = scene.pulse_samples
    t = np.arange(n, dtype=np.float64) / scene.fs
    replica = np.zeros(scene.nr, np.complex128)
    replica[:n] = np.exp(1j * np.pi * scene.kr * t**2)
    return np.conj(np.fft.fft(replica)).astype(np.complex64)


def migration_factor(scene: Scene) -> np.ndarray:
    """D(f_a) = sqrt(1 - (lambda f_a / 2 v)^2) over FFT-ordered Doppler
    bins (zero Doppler centroid), (na,) float64."""
    fa = np.fft.fftfreq(scene.na, d=1.0 / scene.prf)
    s = (scene.wavelength * fa / (2.0 * scene.v)) ** 2
    return np.sqrt(np.maximum(1.0 - s, 1e-12))


def range_gates(scene: Scene) -> np.ndarray:
    """Closest-approach range of each range gate (m), (nr,) float64."""
    return scene.r0 + (np.arange(scene.nr) - scene.nr / 2) * scene.dr


def _phase_table(rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """exp(i rows[:, None] * cols[None, :]) as complex64, in float64 row
    blocks on a few host threads."""
    out = np.empty((rows.size, cols.size), np.complex64)
    step = max(1, rows.size // 32)

    def fill(lo):
        out[lo:lo + step] = np.exp(1j * (rows[lo:lo + step, None] *
                                         cols[None, :]))

    workers = max(1, min(8, os.cpu_count() or 1))
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(0, rows.size, step)))
    return out


def rcmc_filter(scene: Scene) -> np.ndarray:
    """Phase ramp shifting Doppler row f_a by -s(f_a) range samples,
    s = r0 (1/D - 1) / dr, applied to range-FFT bins; (na, nr) complex64."""
    s = scene.r0 * (1.0 / migration_factor(scene) - 1.0) / scene.dr
    return _phase_table(s, 2.0 * np.pi * np.fft.fftfreq(scene.nr))


def azimuth_filter(scene: Scene) -> np.ndarray:
    """Exact hyperbolic azimuth filter, bulk carrier removed:
    exp(i 4 pi fc (D(f_a) - 1) r0(r) / c); (na, nr) complex64."""
    v = 4.0 * np.pi * scene.fc * (migration_factor(scene) - 1.0) / C
    return _phase_table(v, range_gates(scene))


def filters(scene: Scene) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    return range_filter(scene), rcmc_filter(scene), azimuth_filter(scene)


# ---------------------------------------------------------------------------
# Focusing
# ---------------------------------------------------------------------------

def transform_fft(x, axis: int, inverse: bool):
    import jax.numpy as jnp

    return (jnp.fft.ifft if inverse else jnp.fft.fft)(x, axis=axis)


def _split_bf16(x):
    """x = hi + lo + rest, hi and lo holding bf16 values in f32 arrays.
    ``reduce_precision`` keeps the rounding: a round trip through a bf16
    cast may be folded away by XLA's excess-precision rewrites."""
    import jax

    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _dot_high(spec: str, a, b):
    """Real product in three bf16 passes (hi.hi + hi.lo + lo.hi), each
    exact, f32 accumulation."""
    import jax
    import jax.numpy as jnp

    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def dot(p, q):
        return jnp.einsum(spec, p, q, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _cdot_high(spec: str, ar, ai, br, bi):
    return (_dot_high(spec, ar, br) - _dot_high(spec, ai, bi),
            _dot_high(spec, ar, bi) + _dot_high(spec, ai, br))


@functools.lru_cache(maxsize=8)
def _four_step_tables(n: int, inverse: bool):
    """(n1, n2, F1 (n1, n1), F2 (n2, n2), twiddles (n1, n2)) as float32
    re/im pairs, built in float64."""
    n1 = 1 << ((n.bit_length() - 1 + 1) // 2)
    n2 = n // n1
    sign = 1.0 if inverse else -1.0

    def cis(a):
        e = np.exp(sign * 2j * np.pi * a)
        return e.real.astype(np.float32), e.imag.astype(np.float32)

    f1 = cis(np.outer(np.arange(n1), np.arange(n1)) / n1)
    f2 = cis(np.outer(np.arange(n2), np.arange(n2)) / n2)
    tw = cis(np.outer(np.arange(n1), np.arange(n2)) / n)
    return n1, n2, f1, f2, tw


def transform_high(x, axis: int, inverse: bool):
    """DFT along ``axis`` as two matrix-product stages (four-step, n = n1
    n2) with bf16x3 products: the control's lower-precision transform."""
    import jax.numpy as jnp

    x = jnp.moveaxis(x, axis, -1)
    lead, n = x.shape[:-1], x.shape[-1]
    n1, n2, (f1r, f1i), (f2r, f2i), (twr, twi) = _four_step_tables(n, inverse)
    # input index j = j1 + n1 j2 -> (j2, j1); output k = k2 + n2 k1 -> (k1, k2)
    xr = jnp.real(x).reshape(*lead, n2, n1)
    xi = jnp.imag(x).reshape(*lead, n2, n1)
    yr, yi = _cdot_high("...ab,ac->...bc", xr, xi, f2r, f2i)
    yr, yi = yr * twr - yi * twi, yr * twi + yi * twr
    zr, zi = _cdot_high("kb,...bc->...kc", f1r, f1i, yr, yi)
    z = (zr + 1j * zi).astype(jnp.complex64).reshape(*lead, n)
    if inverse:
        z = z / n
    return jnp.moveaxis(z, -1, axis)


TRANSFORMS = {"fft": transform_fft, "high": transform_high}


def _focus(echo, h_r, g_rcmc, h_a, *, transform):
    t = transform
    x = t(t(echo, -1, False) * h_r, -1, True)        # range compression
    x = t(x, -2, False)                               # azimuth FFT
    x = t(t(x, -1, False) * g_rcmc, -1, True)         # RCMC (Fourier shift)
    return t(x * h_a, -2, True)                       # azimuth compression


class Reference:
    """The reference for one scene geometry: filters on the device, one
    compiled focusing call per echo shape."""

    def __init__(self, scene: Scene, transform: str = "fft"):
        import jax

        self.scene = scene
        self.filters = [jax.device_put(f) for f in filters(scene)]
        self._fn = jax.jit(functools.partial(
            _focus, transform=TRANSFORMS[transform]))

    def __call__(self, echo):
        """Device image of one echo (na, nr) or a batch (B, na, nr)."""
        import jax

        with jax.default_matmul_precision("highest"):
            return self._fn(echo, *self.filters)
