"""A non-square scene whose range length is no power of two (128 x 384,
range split 24 x 16) through the normal paths: the jitted fused3
pipeline and the focusing service, each against the benchmark's plain
reference (``sarbench/reference.py``: textbook RDA with ``jnp.fft`` at
``Precision.HIGHEST``), and the three-pass bf16 control against it."""
import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sar import (
    SceneConfig,
    build_pipeline,
    paper_targets,
    simulate_cached,
)
from repro.service import FocusService, LocalBackend, ServiceConfig
from sarbench import reference, scene as benchscene

PARAMS = dict(na=128, nr=384, fc=10.0e9, bandwidth=100.0e6,
              tp=96 / 120.0e6, fs=120.0e6, prf=400.0, v=100.0, r0=5000.0,
              aperture_time=80 / 400.0, noise_db=20.0)
CFG = SceneConfig(**PARAMS)
# the power-of-two 128 x 128 cell of the benchmark's CPU tests uses the
# same limit: f32 reads about 3e-7 against the reference there (and here),
# the bf16x3 control about 1e-5, so one precision step down fails it
L2_LIMIT = 3e-6


def l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def echo():
    return simulate_cached(CFG, paper_targets(CFG))


@pytest.fixture(scope="module")
def ref_image(echo):
    return np.asarray(reference.Reference(benchscene.Scene(**PARAMS))(
        jnp.asarray(echo)))


def test_range_dispatch_runs_the_scene_length_unpadded():
    pipe = build_pipeline(CFG, "fused3", precision="f32")
    assert pipe.spectral_dispatches() == [
        ("azimuth_fft", 0, 128, (16, 8)),
        ("range_comp_rcmc", 1, 384, (24, 16)),
        ("azimuth_compression", 0, 128, (16, 8))]


def test_jitted_fused3_matches_the_reference(echo, ref_image):
    pipe = build_pipeline(CFG, "fused3", precision="f32")
    img = np.asarray(pipe.jitted()(jnp.asarray(echo)[None]))[0]
    assert img.shape == (128, 384)
    assert l2(img, ref_image) < L2_LIMIT


def test_service_matches_the_reference(echo, ref_image, tmp_path,
                                       monkeypatch):
    """The service's warm sweep and its route (fused3, or its fused1
    megakernel twin where the scene fits) take the new length."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))

    async def main():
        svc = FocusService(ServiceConfig(max_batch=2, max_delay_ms=50.0,
                                         precision="f32"),
                           backend=LocalBackend())
        await svc.start(warm=[(CFG, "fused3", "f32")])
        outs = await asyncio.gather(svc.focus(echo, CFG, variant="fused3"),
                                    svc.focus(echo, CFG, variant="fused3"))
        await svc.stop()
        return outs

    for img in asyncio.run(main()):
        assert l2(np.asarray(img), ref_image) < L2_LIMIT


def test_three_pass_bf16_control_fails_the_limit(echo, ref_image):
    control = reference.Reference(benchscene.Scene(**PARAMS), "high")
    assert l2(np.asarray(control(jnp.asarray(echo))), ref_image) > L2_LIMIT


def test_each_dispatch_is_named_by_length_and_split_inside_its_step():
    """A profile attributes the range kernel to its step (the outermost
    scope) and names its transform within it."""
    import re

    import jax

    pipe = build_pipeline(CFG, "fused3", precision="f32")
    x = jax.ShapeDtypeStruct((1, 128, 384), jnp.complex64)
    text = pipe.jitted().lower(x).as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(focus_fused3\)/[^"]*)"', text))
    for step, _, n, fs in pipe.spectral_dispatches():
        scope = f"jit(focus_fused3)/{step}/dft{n}_{fs[0]}x{fs[1]}/"
        assert any(name.startswith(scope) for name in names), scope
