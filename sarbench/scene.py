"""Seeded stripmap echoes for the benchmark: point targets plus noise.

The benchmark's own copy of the point-target simulator, so that what the
benchmark feeds the program does not depend on the program. The physics is
that of the paper's Sec. V-A simulator: the hyperbolic range equation

    R_k(eta) = sqrt(r0_k^2 + v^2 (eta - eta_k)^2),

a linear-FM chirp, rectangular range and azimuth windows, float64 phase
math, complex64 storage and circular Gaussian noise at the configured raw
SNR. Each target is evaluated only over its support (its aperture lines x
the range samples its pulse can reach), not over the whole grid, so a
4096 x 4096 scene takes a fraction of a second on the host. Noise is drawn
with ``jax.random`` on the default device, one echo per key.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

C = 299_792_458.0  # speed of light, m/s


@dataclasses.dataclass(frozen=True)
class Scene:
    """One stripmap acquisition grid (azimuth lines x range samples)."""

    na: int
    nr: int
    fc: float             # carrier (Hz)
    bandwidth: float      # chirp bandwidth (Hz)
    tp: float             # pulse length (s)
    fs: float             # range sampling rate (Hz)
    prf: float            # pulse repetition frequency (Hz)
    v: float              # effective platform velocity (m/s)
    r0: float             # closest-approach range of the scene centre (m)
    aperture_time: float  # synthetic aperture (s)
    noise_db: float       # raw-data SNR (dB)

    @property
    def wavelength(self) -> float:
        return C / self.fc

    @property
    def kr(self) -> float:
        return self.bandwidth / self.tp

    @property
    def ka(self) -> float:
        return 2.0 * self.v**2 / (self.wavelength * self.r0)

    @property
    def doppler_bandwidth(self) -> float:
        return self.ka * self.aperture_time

    @property
    def dr(self) -> float:
        return C / (2.0 * self.fs)

    @property
    def da(self) -> float:
        return self.v / self.prf

    @property
    def pulse_samples(self) -> int:
        return int(round(self.tp * self.fs))

    @property
    def aperture_samples(self) -> int:
        return int(round(self.aperture_time * self.prf))

    def validate(self) -> None:
        if self.doppler_bandwidth >= self.prf:
            raise ValueError(f"azimuth aliasing: Doppler bandwidth "
                             f"{self.doppler_bandwidth:.1f} Hz >= PRF "
                             f"{self.prf:.1f} Hz")
        if self.bandwidth > self.fs:
            raise ValueError("range aliasing: bandwidth > fs")
        if self.pulse_samples >= self.nr:
            raise ValueError("pulse longer than the range window")
        if self.aperture_samples >= self.na:
            raise ValueError("aperture longer than the azimuth window")


@dataclasses.dataclass(frozen=True)
class Target:
    range_offset: float    # m from the scene centre, + = farther
    azimuth_offset: float  # m from the scene centre, + = later
    sigma: float = 1.0


SCENE_KEYS = tuple(f.name for f in dataclasses.fields(Scene))


def scene_from_config(config: dict) -> Scene:
    """The ``scene`` group of a configuration file."""
    s = config["scene"]
    missing = [k for k in SCENE_KEYS if k not in s]
    if missing:
        raise KeyError(f"configuration scene lacks {missing}")
    return Scene(**{k: s[k] for k in SCENE_KEYS})


def targets_from_config(config: dict, scene: Scene) -> list[Target]:
    """``targets``: [range, azimuth, sigma] with range and azimuth in units
    of the scene's extent (nr * dr and na * da), so a pattern carries over
    between grids."""
    return [Target(r * scene.dr * scene.nr, a * scene.da * scene.na, s)
            for r, a, s in config["targets"]]


def time_axes(scene: Scene) -> tuple[np.ndarray, np.ndarray]:
    """(slow time (na,), fast time (nr,)), float64, centred on the scene."""
    eta = (np.arange(scene.na, dtype=np.float64) - scene.na / 2) / scene.prf
    t0 = 2.0 * scene.r0 / C
    t = t0 + (np.arange(scene.nr, dtype=np.float64) - scene.nr / 2) / scene.fs
    return eta, t


def target_patch(scene: Scene, eta: np.ndarray, t: np.ndarray,
                 tgt: Target):
    """(row slice, column slice, complex64 patch) holding every non-zero
    sample of one target's echo; None if the target lies off the grid."""
    r0k = scene.r0 + tgt.range_offset
    etak = tgt.azimuth_offset / scene.v
    rows = np.nonzero(np.abs(eta - etak) <= scene.aperture_time / 2)[0]
    if rows.size == 0:
        return None
    rs = slice(int(rows[0]), int(rows[-1]) + 1)
    e = eta[rs]
    rk = np.sqrt(r0k**2 + (scene.v * (e - etak)) ** 2)[:, None]
    tau = 2.0 * rk / C
    # columns the pulse can reach on any of these lines, one sample of
    # margin each side; outside them the range window is closed
    c_lo = math.floor((tau.min() - t[0]) * scene.fs) - 1
    c_hi = math.ceil((tau.max() + scene.tp - t[0]) * scene.fs) + 2
    c_lo, c_hi = max(c_lo, 0), min(c_hi, scene.nr)
    if c_lo >= c_hi:
        return None
    cs = slice(c_lo, c_hi)
    dt = t[None, cs] - tau
    w_r = (np.abs(dt - scene.tp / 2) <= scene.tp / 2).astype(np.float64)
    phase = -2.0 * np.pi * scene.fc * tau + np.pi * scene.kr * dt**2
    patch = tgt.sigma * w_r * np.exp(1j * phase)
    return rs, cs, patch.astype(np.complex64)


def targets_image(scene: Scene, targets: list[Target]) -> np.ndarray:
    """Noise-free echo (na, nr) complex64: the targets' echoes summed in
    order, each over its support only."""
    scene.validate()
    eta, t = time_axes(scene)
    acc = np.zeros((scene.na, scene.nr), np.complex64)
    for tgt in targets:
        p = target_patch(scene, eta, t, tgt)
        if p is not None:
            rs, cs, patch = p
            acc[rs, cs] = acc[rs, cs] + patch
    return acc


def noise_sigma(scene: Scene) -> float:
    """Per-component noise deviation: the echo's power on its support is
    sigma^2 = 1, so the noise power is 1 / SNR."""
    snr = 10.0 ** (scene.noise_db / 10.0)
    return float(np.sqrt(1.0 / (2.0 * snr)))


def echo_keys(seed: int, count: int):
    """``count`` distinct PRNG keys from one seed of any size."""
    import jax

    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    base = jax.random.wrap_key_data(words, impl="threefry2x32")
    return [jax.random.key_data(jax.random.fold_in(base, i))
            for i in range(count)]


def _noisy(clean, key_data, sigma):
    import jax
    import jax.numpy as jnp

    key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
    k1, k2 = jax.random.split(key)
    noise = (jax.random.normal(k1, clean.shape, jnp.float32) +
             1j * jax.random.normal(k2, clean.shape, jnp.float32)) * sigma
    return clean + noise.astype(jnp.complex64)


def make_echoes(scene: Scene, targets: list[Target], seed: int,
                count: int) -> list[np.ndarray]:
    """``count`` distinct host echoes (na, nr) complex64 from ``seed``: the
    same targets, each echo with its own noise, made on the device in one
    compiled call per echo and copied back."""
    import jax

    clean = jax.device_put(targets_image(scene, targets))
    fn = jax.jit(_noisy, static_argnums=2)
    sigma = noise_sigma(scene)
    return [np.asarray(fn(clean, k, sigma)) for k in echo_keys(seed, count)]
