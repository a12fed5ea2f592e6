"""The support-only generator against the program's full-grid simulator."""
import numpy as np
import pytest

from sarbench import scene as S

PATTERN = {"targets": [[0.0, 0.0, 1.0], [0.125, 0.0, 1.0], [0.0, 0.125, 1.0],
                       [-0.125, -0.125, 1.0], [0.25, 0.1875, 1.0]]}


@pytest.fixture(scope="module")
def program():
    from repro.core.sar import paper_targets, simulate, test_scene

    cfg = test_scene(256)
    return cfg, paper_targets(cfg), simulate


def _scene(cfg):
    return S.Scene(**{k: getattr(cfg, k) for k in S.SCENE_KEYS})


def test_pattern_is_the_papers_targets(program):
    cfg, targets, _ = program
    mine = S.targets_from_config(PATTERN, _scene(cfg))
    assert [(t.range_offset, t.azimuth_offset, t.sigma) for t in mine] == \
        [(t.range_offset, t.azimuth_offset, t.sigma) for t in targets]


def test_support_only_targets_equal_full_grid(program):
    cfg, targets, simulate = program
    sc = _scene(cfg)
    ours = S.targets_image(sc, S.targets_from_config(PATTERN, sc))
    theirs = simulate(cfg, targets, add_noise=False)
    scale = np.abs(theirs).max()
    np.testing.assert_allclose(ours, theirs, rtol=0, atol=2e-7 * scale)


def test_noisy_echo_equals_simulator_with_the_same_key(program):
    import jax

    cfg, targets, simulate = program
    sc = _scene(cfg)
    clean = S.targets_image(sc, S.targets_from_config(PATTERN, sc))
    key = jax.random.key_data(jax.random.PRNGKey(cfg.seed))
    ours = np.asarray(jax.jit(S._noisy, static_argnums=2)(
        clean, key, S.noise_sigma(sc)))
    theirs = simulate(cfg, targets)
    np.testing.assert_allclose(ours, theirs, rtol=0,
                               atol=2e-7 * np.abs(theirs).max())


def test_echoes_are_seeded_distinct_and_take_large_seeds(program):
    cfg, _, _ = program
    sc = _scene(cfg)
    tg = S.targets_from_config(PATTERN, sc)
    seed = 2**31 + 12345
    a = S.make_echoes(sc, tg, seed, 2)
    b = S.make_echoes(sc, tg, seed, 2)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert np.abs(a[0] - a[1]).max() > 0.1
    c = S.make_echoes(sc, tg, seed + 1, 1)
    assert np.abs(a[0] - c[0]).max() > 0.1
