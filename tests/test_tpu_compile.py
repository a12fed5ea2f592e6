"""Compile rehearsal of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached: each test lowers one dispatch of the paper's
4096 x 4096 scene with arguments placed on a described v5e device and
asks Mosaic to compile it. Interpret mode cannot see what these catch:
lane-dimension reshapes Mosaic cannot hold, block shapes off the (8, 128)
tiling, scoped-VMEM overruns, dtypes the matrix unit does not take.

The topology is described inside a module fixture, never at import (only
one process may load the TPU library at a time; a pytest-xdist worker
that collects this file must not take it). ``fft4step.device_kind`` is
pointed at the v5e row of the device table for each test, since
``jax.devices()`` still reports the host CPU here.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sar import SceneConfig, build_pipeline, paper_scene
from repro.kernels import fft4step, ops
from repro.kernels.interleave import interleave
from repro.tuning import cost

V5E = "TPU v5 lite"
N = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_v5e(monkeypatch):
    """Size kernels from the v5e row and keep the persistent compile cache
    off: an executable for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(fft4step, "device_kind", lambda: V5E)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.fixture(scope="module")
def paper_cfg():
    return paper_scene()


@pytest.mark.parametrize("step", [0, 1, 2],
                         ids=["azimuth_fft", "range_shared_outer",
                              "azimuth_compression_outer"])
@pytest.mark.parametrize("precision",
                         sorted({"f32", cost.device_spec(V5E).serving_tier}))
def test_fused3_dispatch_compiles_for_v5e(on_v5e, one_chip, paper_cfg, step,
                                          precision):
    pipe = build_pipeline(paper_cfg, "fused3", precision=precision,
                          interpret=False, tune="off")
    assert pipe.steps[step].kernel_kw["block"] % 128 == 0
    x = jax.ShapeDtypeStruct((1, N, N), jnp.complex64, sharding=one_chip)
    _compile(pipe.steps[step].fn, x)


# the ERS-1/2 image-mode block (sarbench/configs/ers_cband_8192.json) cut
# to 256 lines: its range steps run the 6144-point transform as 48 x 128
ERS_LINES = SceneConfig(na=256, nr=6144, fc=5.3e9, bandwidth=15.55e6,
                        tp=37.12e-6, fs=18.96e6, prf=1679.9, v=7100.0,
                        r0=850e3, aperture_time=0.1, noise_db=20.0)


@pytest.mark.parametrize("step", [0, 1, 2],
                         ids=["azimuth_fft", "range_shared_outer",
                              "azimuth_compression_outer"])
def test_fused3_mixed_radix_dispatch_compiles_for_v5e(on_v5e, one_chip,
                                                      step):
    pipe = build_pipeline(ERS_LINES, "fused3", precision="f32",
                          interpret=False, tune="off")
    assert pipe.spectral_dispatches()[1][2:] == (6144, (48, 128))
    x = jax.ShapeDtypeStruct((1, ERS_LINES.na, ERS_LINES.nr), jnp.complex64,
                             sharding=one_chip)
    _compile(pipe.steps[step].fn, x)


@pytest.mark.parametrize("shape", [(4, N, N), (1, 8192, 6144)],
                         ids=["paper_batch4", "ers_block"])
def test_image_interleave_compiles_for_v5e(on_v5e, one_chip, shape):
    """The kernel that writes the focused image as interleaved f32, at
    both cells' image sizes."""
    f32 = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compile(lambda xr, xi: interleave(xr, xi, interpret=False),
                    f32, f32)
    assert f"f32[{shape[0] * shape[1]},{2 * shape[2]}]" in text


def test_range_dispatch_shared_filter_compiles_for_v5e(on_v5e, one_chip):
    """The fused range compression of fused_tfree and the paper's
    pipeline: FFT · H_r · IFFT with one shared filter vector."""
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    _compile(lambda xr, xi, hr, hi: ops.fused_fft_mult_ifft_rows(
        xr, xi, hr, hi, interpret=False), f32(1, N, N), f32(1, N, N),
        f32(N), f32(N))


def test_fused1_staged_megakernel_compiles_for_v5e(on_v5e, one_chip,
                                                   paper_cfg):
    pipe = build_pipeline(paper_cfg, "fused1", interpret=False, tune="off")
    (step,) = pipe.steps
    assert step.kernel_kw["residency"] == "staged"
    x = jax.ShapeDtypeStruct((1, N, N), jnp.complex64, sharding=one_chip)
    _compile(step.fn, x)


@pytest.mark.parametrize("precision", ["bs16", "f16"])
def test_f16_operand_tiers_refused_on_v5e(on_v5e, precision):
    """v5e's MXU takes no float16 operands (Mosaic cannot even pack f32 to
    f16 there): building such a kernel raises and names the device."""
    spec = fft4step.SpectralSpec(n=N, fwd=True, inv=True,
                                 filter_mode="none", block=128,
                                 precision=precision)
    with pytest.raises(ValueError, match=V5E):
        fft4step.build_spectral_call(spec, lines=N, interpret=False)
    assert cost.device_spec(V5E).serving_tier == "f32"


def test_vmem_limit_never_exceeds_the_chip(on_v5e):
    spec = cost.device_spec(V5E)
    params = fft4step.compiler_params(spec.vmem_bytes - 1, interpret=False)
    assert params.vmem_limit_bytes <= spec.vmem_bytes
    with pytest.raises(ValueError, match="VMEM"):
        fft4step.compiler_params(spec.vmem_bytes + 1, interpret=False)
    assert np.isfinite(spec.peak_hbm_bytes)
