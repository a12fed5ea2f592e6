"""The plain reference against direct jnp.fft composition, and the
control's transform against the FFT."""
import numpy as np
import pytest

from sarbench import quality, reference as R, scene as S
from sarbench.tests.test_scene import PATTERN


@pytest.fixture(scope="module")
def case():
    from repro.core.sar import test_scene

    cfg = test_scene(256)
    sc = S.Scene(**{k: getattr(cfg, k) for k in S.SCENE_KEYS})
    echo = S.make_echoes(sc, S.targets_from_config(PATTERN, sc), 3, 1)[0]
    return cfg, sc, echo


def test_reference_is_the_textbook_composition(case):
    import jax.numpy as jnp

    _, sc, echo = case
    h_r, g, h_a = R.filters(sc)
    x = jnp.fft.ifft(jnp.fft.fft(echo, axis=1) * h_r, axis=1)
    x = jnp.fft.fft(x, axis=0)
    x = jnp.fft.ifft(jnp.fft.fft(x, axis=1) * g, axis=1)
    want = np.asarray(jnp.fft.ifft(x * h_a, axis=0))
    got = np.asarray(R.Reference(sc)(echo))
    assert quality.l2_relative_error(got, want) < 1e-6


def test_filters_in_float64(case):
    _, sc, _ = case
    _, g, h_a = R.filters(sc)
    s = sc.r0 * (1.0 / R.migration_factor(sc) - 1.0) / sc.dr
    k = np.fft.fftfreq(sc.nr)
    np.testing.assert_allclose(g, np.exp(2j * np.pi * np.outer(s, k)),
                               atol=1e-6)
    v = 4 * np.pi * sc.fc * (R.migration_factor(sc) - 1.0) / S.C
    np.testing.assert_allclose(h_a, np.exp(1j * np.outer(v, R.range_gates(sc))),
                               atol=1e-6)


def test_reference_focuses_every_target_where_expected(case):
    _, sc, echo = case
    img = np.asarray(R.Reference(sc)(echo))
    targets = S.targets_from_config(PATTERN, sc)
    for (r, c, snr), t in zip(quality.target_reports(img, sc, targets),
                              targets):
        er, ec = quality.expected_pixel(sc, t)
        assert abs(r - er) <= 1 and abs(c - ec) <= 1
        assert snr > 40.0


def test_reference_agrees_with_the_programs_xla_pipeline(case):
    """A second witness: the program's unfused XLA route."""
    from repro.core.sar import build_pipeline

    cfg, sc, echo = case
    theirs = np.asarray(build_pipeline(cfg, "unfused",
                                       rcmc_mode="fourier").run(echo))
    assert quality.l2_relative_error(theirs, np.asarray(
        R.Reference(sc)(echo))) < 1e-5


@pytest.mark.parametrize("n, axis, inverse", [(256, -1, False),
                                              (128, -2, True),
                                              (512, -1, True)])
def test_four_step_transform_is_a_dft(n, axis, inverse):
    import jax.numpy as jnp

    rng = np.random.default_rng(n)
    x = (rng.standard_normal((8, n)) + 1j * rng.standard_normal((8, n))
         ).astype(np.complex64)
    if axis == -2:
        x = np.ascontiguousarray(x.T)
    want = (np.fft.ifft if inverse else np.fft.fft)(
        x.astype(np.complex128), axis=axis)
    got = np.asarray(R.transform_high(jnp.asarray(x), axis, inverse))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    # three bf16 passes: well above f32 rounding, far below bf16's
    assert 1e-7 < err < 1e-4


def test_control_is_measurably_below_f32(case):
    _, sc, echo = case
    ref = np.asarray(R.Reference(sc)(echo))
    ctl = np.asarray(R.Reference(sc, "high")(echo))
    assert 3e-6 < quality.l2_relative_error(ctl, ref) < 1e-4
