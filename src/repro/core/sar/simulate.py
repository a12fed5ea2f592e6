"""Chirp-signal point-target raw-echo simulator (paper Sec. V-A).

Generates the demodulated baseband echo matrix (na x nr, complex64) for a set
of point targets under the hyperbolic range equation

    R_k(eta) = sqrt(r0_k^2 + v^2 (eta - eta_k)^2),

with a linear-FM transmitted chirp and rectangular range/azimuth windows, plus
additive circular Gaussian noise at the configured raw SNR (paper: 20 dB).

Pure jnp, vectorized over the full (na, nr) grid per target, on the host
CPU device (see :func:`simulate`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sar.geometry import C, PointTarget, SceneConfig


def time_axes(cfg: SceneConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(slow_time (na,), fast_time (nr,)) centered on the scene center."""
    eta = (jnp.arange(cfg.na, dtype=jnp.float64) - cfg.na / 2) / cfg.prf
    # fast time window centered on the scene-center two-way delay
    t0 = 2.0 * cfg.r0 / C
    t = t0 + (jnp.arange(cfg.nr, dtype=jnp.float64) - cfg.nr / 2) / cfg.fs
    return eta, t


def _target_echo(cfg: SceneConfig, eta, t, tgt: PointTarget) -> jnp.ndarray:
    """Echo of one point target on the (na, nr) grid, complex64."""
    r0k = cfg.r0 + tgt.range_offset
    etak = tgt.azimuth_offset / cfg.v
    # instantaneous slant range, (na, 1)
    rk = jnp.sqrt(r0k**2 + (cfg.v * (eta - etak)) ** 2)[:, None]
    tau = 2.0 * rk / C                       # two-way delay
    dt = t[None, :] - tau                    # fast time relative to echo start
    # windows
    w_r = (jnp.abs(dt - cfg.tp / 2) <= cfg.tp / 2).astype(jnp.float64)
    w_a = (jnp.abs(eta - etak) <= cfg.aperture_time / 2).astype(jnp.float64)[:, None]
    # carrier phase + chirp phase (float64 host math keeps 2*pi*fc*tau exact
    # enough; the stored echo is complex64 like the paper's FP32 data)
    phase = -2.0 * jnp.pi * cfg.fc * tau + jnp.pi * cfg.kr * dt**2
    echo = tgt.sigma * w_r * w_a * jnp.exp(1j * phase)
    return echo.astype(jnp.complex64)


def simulate(cfg: SceneConfig, targets: list[PointTarget],
             add_noise: bool = True) -> np.ndarray:
    """Raw echo matrix (na, nr) complex64 for all targets (+ noise).

    Runs on the host CPU device whatever the default backend: the float64
    phase math has no TPU lowering, and making the input is set-up, not
    the focusing path. Returns a host array, so the caller's first jitted
    use places it on the caller's device."""
    cfg.validate()
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        with jax.enable_x64(True):
            eta, t = time_axes(cfg)
            acc = jnp.zeros((cfg.na, cfg.nr), jnp.complex64)
            for tgt in targets:
                acc = acc + _target_echo(cfg, eta, t, tgt)
        if add_noise and cfg.noise_db is not None:
            # raw per-sample echo power within the support is sigma^2;
            # scale noise for the configured raw SNR
            snr_lin = 10.0 ** (cfg.noise_db / 10.0)
            sigma_n = float(np.sqrt(1.0 / (2.0 * snr_lin)))
            key = jax.random.PRNGKey(cfg.seed)
            k1, k2 = jax.random.split(key)
            noise = (jax.random.normal(k1, acc.shape, jnp.float32) +
                     1j * jax.random.normal(k2, acc.shape, jnp.float32)
                     ) * sigma_n
            acc = acc + noise.astype(jnp.complex64)
        return np.asarray(acc)


@functools.lru_cache(maxsize=4)
def _cached_scene_np(cfg: SceneConfig, targets: tuple[PointTarget, ...],
                     add_noise: bool) -> np.ndarray:
    return simulate(cfg, list(targets), add_noise)


def simulate_cached(cfg: SceneConfig, targets: list[PointTarget],
                    add_noise: bool = True) -> np.ndarray:
    """Host-cached simulator (tests reuse the same scene repeatedly)."""
    return _cached_scene_np(cfg, tuple(targets), add_noise)
