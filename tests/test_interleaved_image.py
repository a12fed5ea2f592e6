"""The image contract of ``Pipeline.jitted()``: the program returns one
interleaved f32 array ``(B, na, 2·nr)``, and the host sees complex64
``(B, na, nr)`` through a zero-copy view, equal to ``Pipeline.run``'s image.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as planlib
from repro.core.sar import build_pipeline, paper_targets, simulate_cached
from repro.core.sar.geometry import test_scene as make_test_scene
from repro.kernels.interleave import interleave
from repro.service import BatchKey, LocalBackend

CFG = make_test_scene(128)
# the 128 x 384 scene of test_mixed_radix_scene.py: range split 24 x 16
MIXED = dataclasses.replace(
    CFG, nr=384, fc=10.0e9, bandwidth=100.0e6, tp=96 / 120.0e6, fs=120.0e6,
    prf=400.0, v=100.0, r0=5000.0, aperture_time=80 / 400.0, noise_db=20.0)


def echoes(cfg, b):
    raw = np.asarray(simulate_cached(cfg, paper_targets(cfg)))
    return jnp.asarray(np.stack([raw * (1.0 + 0.25 * k) for k in range(b)]))


@pytest.mark.parametrize("cfg,variant,b", [
    (CFG, "fused3", 1),
    (CFG, "fused3", 2),
    (MIXED, "fused3", 1),
    (CFG, "fused1", 2),
    (CFG, "unfused", 2),
], ids=["fused3-b1", "fused3-b2", "fused3-128x384", "fused1", "unfused"])
def test_host_image_is_bit_identical_to_run(cfg, variant, b):
    pipe = build_pipeline(cfg, variant, precision="f32")
    x = echoes(cfg, b)
    img = np.asarray(pipe.jitted()(x))
    assert img.dtype == np.complex64 and img.shape == (b, cfg.na, cfg.nr)
    assert np.array_equal(img, np.asarray(jax.jit(pipe.run)(x)))


def test_result_reports_complex64_and_views_the_f32_copy():
    pipe = build_pipeline(CFG, "fused3")
    y = pipe.jitted()(echoes(CFG, 2))
    assert isinstance(y, planlib.FocusedImages)
    assert y.shape == (2, CFG.na, CFG.nr)
    assert y.dtype == np.complex64
    assert y.interleaved.dtype == jnp.float32
    assert y.interleaved.shape == (2, CFG.na, 2 * CFG.nr)
    assert jax.block_until_ready(y) is y
    img = np.asarray(y)
    assert img.dtype == np.complex64 and not img.flags.owndata
    assert img.base is not None and img.base.dtype == np.float32
    assert np.shares_memory(img, np.asarray(y.interleaved))
    # an explicit dtype or copy converts; the device array is complex64
    assert np.asarray(y, np.complex128).dtype == np.complex128
    assert np.array(y, copy=True).flags.owndata
    dev = jnp.asarray(y)
    assert dev.dtype == jnp.complex64
    assert np.array_equal(np.asarray(dev), img)
    assert np.array_equal(np.asarray(y.on_device()), img)
    assert np.array_equal(np.asarray(y.at[0].multiply(2.0)[1]), img[1])


def test_lowered_program_returns_interleaved_f32():
    pipe = build_pipeline(CFG, "fused3")
    x = jax.ShapeDtypeStruct((2, CFG.na, CFG.nr), jnp.complex64)
    text = pipe.jitted().lower(x).as_text()
    (sig,) = re.findall(r"func\.func public @main\((.*)", text)
    result = sig.split("->", 1)[1]
    assert "tensor<2x128x256xf32>" in result
    assert "complex" not in result


def test_interleave_runs_under_the_last_steps_unsplit_scope():
    pipe = build_pipeline(CFG, "fused3")
    x = jax.ShapeDtypeStruct((1, CFG.na, CFG.nr), jnp.complex64)
    text = pipe.jitted().lower(x).as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(focus_fused3\)/[^"]*)"', text))
    last = pipe.steps[-1].name
    inner = {n for n in names if "jit(interleave)" in n}
    assert inner and all(
        n.startswith(f"jit(focus_fused3)/{last}/unsplit/") for n in inner)
    assert {n.split("/")[1] for n in names} == {s.name for s in pipe.steps}


@pytest.mark.parametrize("shape", [(2, 128, 128), (3, 40, 96), (256, 1024)],
                         ids=["tiled", "ragged", "blocks"])
def test_interleave_kernel_moves_every_bit(shape):
    """Pure data movement: each f32 plane's bits land in alternate
    elements, signed zeros, subnormals, infinities and NaNs included."""
    rng = np.random.default_rng(18)
    xr = rng.standard_normal(shape).astype(np.float32)
    xi = rng.standard_normal(shape).astype(np.float32)
    xr.flat[:4] = [-0.0, 1e-40, np.inf, np.nan]
    xi.flat[:4] = [np.nan, -np.inf, -0.0, -1e-40]
    y = np.asarray(interleave(jnp.asarray(xr), jnp.asarray(xi)))
    assert y.shape == (*shape[:-1], 2 * shape[-1])
    assert np.array_equal(y[..., 0::2].view(np.uint32), xr.view(np.uint32))
    assert np.array_equal(y[..., 1::2].view(np.uint32), xi.view(np.uint32))


def test_local_backend_pads_slices_and_warms_on_the_new_result():
    """execute pads a 3-scene batch to the 4 bucket and slices it back;
    warm pre-traces every bucket; both go through the interleaved result."""
    backend = LocalBackend(sweep=((None, None),), fused1="off")
    key = BatchKey(CFG, "fused3", None, False)
    backend.warm(key, max_batch=4)
    x = np.asarray(echoes(CFG, 3))
    out = backend.execute(key, x)
    assert out.shape == (3, CFG.na, CFG.nr) and out.dtype == np.complex64
    ref = np.asarray(jax.jit(build_pipeline(CFG, "fused3").run)(
        jnp.asarray(x)))
    assert np.array_equal(out, ref)
