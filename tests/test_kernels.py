"""Per-kernel allclose sweeps + hypothesis property tests vs the jnp oracle."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - see requirements-dev.txt
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.transpose import transpose

RNG = np.random.default_rng(7)


def rand(*shape):
    return RNG.standard_normal(shape).astype(np.float32)


def assert_close(got, want, tol=2e-4):
    gr, gi = got
    wr, wi = want
    scale = max(float(jnp.max(jnp.abs(wr))), float(jnp.max(jnp.abs(wi))), 1e-30)
    np.testing.assert_allclose(np.asarray(gr), np.asarray(wr),
                               atol=tol * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(wi),
                               atol=tol * scale, rtol=0)


# ---------------------------------------------------------------------------
# Shape / impl / axis sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["matmul", "stockham"])
@pytest.mark.parametrize("n", [16, 64, 256, 1024])
@pytest.mark.parametrize("axis", [0, 1])
def test_fft_sweep(impl, n, axis):
    lines = 6
    shape = (lines, n) if axis == 1 else (n, lines)
    xr, xi = rand(*shape), rand(*shape)
    got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi), fwd=True,
                          inv=False, axis=axis, fft_impl=impl, block=2)
    assert_close(got, ref.fft_ref(xr, xi, axis=axis))


@pytest.mark.parametrize("impl", ["matmul", "stockham"])
@pytest.mark.parametrize("n", [64, 512])
def test_ifft_sweep(impl, n):
    xr, xi = rand(4, n), rand(4, n)
    got = ops.ifft_rows(jnp.asarray(xr), jnp.asarray(xi), fft_impl=impl,
                        block=4)
    assert_close(got, ref.ifft_ref(xr, xi, axis=1))


@pytest.mark.parametrize("mode", ["shared", "full", "outer", "shared_outer"])
def test_fused_filter_modes(mode):
    n, lines = 128, 8
    xr, xi = rand(lines, n), rand(lines, n)
    kw = dict(fwd=True, inv=True, axis=1, block=4, filter_mode=mode)
    if mode in ("shared", "full"):
        shape = (n,) if mode == "shared" else (lines, n)
        hr, hi = rand(*shape), rand(*shape)
        got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi),
                              hr=jnp.asarray(hr), hi=jnp.asarray(hi), **kw)
        hb = (hr[None, :], hi[None, :]) if mode == "shared" else (hr, hi)
        want = ref.spectral_ref(xr, xi, axis=1, fwd=True, inv=True,
                                hr=hb[0], hi=hb[1])
    elif mode == "outer":
        u, v = rand(lines, 2), rand(n, 2)
        got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi),
                              u=jnp.asarray(u), v=jnp.asarray(v), **kw)
        want = ref.spectral_ref(xr, xi, axis=1, fwd=True, inv=True, u=u, v=v)
    else:
        hr, hi = rand(n), rand(n)
        u, v = rand(lines), rand(n)
        got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi),
                              hr=jnp.asarray(hr), hi=jnp.asarray(hi),
                              u=jnp.asarray(u), v=jnp.asarray(v), **kw)
        want = ref.spectral_ref(xr, xi, axis=1, fwd=True, inv=True,
                                hr=hr[None, :], hi=hi[None, :], u=u, v=v)
    assert_close(got, want)


@pytest.mark.parametrize("n1,n2", [(8, 8), (16, 4), (32, 32), (128, 8)])
def test_factorizations(n1, n2):
    n = n1 * n2
    xr, xi = rand(4, n), rand(4, n)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), n1=n1, n2=n2,
                       block=4)
    assert_close(got, ref.fft_ref(xr, xi, axis=1))


def _pow2_split_before_mixed_radix(n):
    """The power-of-two rule as it stood before other lengths were
    allowed: the ~sqrt split n1 >= n2, three factors past 128 * 128."""
    p = n.bit_length() - 1
    if n <= 128 * 128:
        n1 = 1 << ((p + 1) // 2)
        return n1, n // n1
    p1 = (p + 2) // 3
    p2 = (p - p1 + 1) // 2
    return 1 << p1, 1 << p2, 1 << (p - p1 - p2)


def test_power_of_two_splits_unchanged_up_to_2_21():
    from repro.kernels.fft4step import default_factorization
    for p in range(22):
        assert default_factorization(1 << p) == \
            _pow2_split_before_mixed_radix(1 << p), 1 << p
    assert default_factorization(4096) == (64, 64)
    assert default_factorization(8192) == (128, 64)
    assert default_factorization(32768) == (32, 32, 32)


@pytest.mark.parametrize("n,want", [(6144, (48, 128)), (384, (24, 16)),
                                    (96, (12, 8)), (5616, (78, 72)),
                                    (7, (7, 1)), (3 * 2 ** 14, (24, 16, 128))])
def test_non_power_of_two_default_split(n, want):
    """The 128-point factor innermost over whole sublane tiles where such
    a split exists, else tiled factors, else the most even split."""
    from repro.kernels.fft4step import default_factorization
    assert default_factorization(n) == want


@pytest.mark.parametrize("n", [2 * 131, 128 ** 3 + 1, 3 * 128 ** 3])
def test_unfactorable_length_raises_naming_it(n):
    from repro.kernels.fft4step import SpectralSpec, default_factorization
    with pytest.raises(ValueError, match=str(n)):
        default_factorization(n)
    with pytest.raises(ValueError, match=str(n)):
        SpectralSpec(n=n, fwd=True, inv=False, filter_mode="none").factors()


def test_factor_past_the_mxu_edge_raises():
    from repro.kernels.fft4step import SpectralSpec
    with pytest.raises(ValueError, match="between 1 and 128"):
        SpectralSpec(n=6144, fwd=True, inv=False, filter_mode="none",
                     n1=24, n2=256).factors()
    assert SpectralSpec(n=6144, fwd=True, inv=False, filter_mode="none",
                        n1=96).factors() == (96, 64)


@pytest.mark.parametrize("n", [96, 384, 6144])
@pytest.mark.parametrize("axis", [0, 1])
def test_non_power_of_two_lengths_match_jnp_fft(n, axis):
    """spectral_axis0/1 at mixed-radix lengths: the forward FFT against
    jnp.fft, and the fused FFT * filter * IFFT against the oracle."""
    lines = 8
    shape = (lines, n) if axis == 1 else (n, lines)
    xr, xi = rand(*shape), rand(*shape)
    ax = 1 if axis == 1 else 0
    got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi), axis=axis,
                          fwd=True, inv=False, block=8)
    want = np.fft.fft(xr + 1j * xi, axis=ax)
    assert_close(got, (want.real, want.imag))
    hr, hi = rand(n), rand(n)
    got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi),
                          hr=jnp.asarray(hr), hi=jnp.asarray(hi), axis=axis,
                          filter_mode="shared", block=8)
    h = (hr + 1j * hi)[None, :] if axis == 1 else (hr + 1j * hi)[:, None]
    want = np.fft.ifft(np.fft.fft(xr + 1j * xi, axis=ax) * h, axis=ax)
    assert_close(got, (want.real, want.imag))


@pytest.mark.parametrize("kw", [dict(fft_impl="stockham"),
                                dict(precision="f16"),
                                dict(precision="bs16")])
def test_power_of_two_only_paths_refuse_other_lengths(kw):
    xr = jnp.asarray(rand(8, 96))
    with pytest.raises(ValueError, match="power-of-two lengths only"):
        ops.spectral_op(xr, xr, axis=1, fwd=True, inv=False, block=8, **kw)


def test_karatsuba_and_bf16():
    xr, xi = rand(4, 512), rand(4, 512)
    want = ref.fft_ref(xr, xi, axis=1)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), karatsuba=True,
                       block=4)
    assert_close(got, want)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), compute_dtype="bf16",
                       block=4)
    assert_close(got, want, tol=5e-2)


def test_line_padding():
    xr, xi = rand(5, 64), rand(5, 64)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=4)
    assert_close(got, ref.fft_ref(xr, xi, axis=1))


@pytest.mark.parametrize("r,c", [(64, 64), (128, 256), (96, 32)])
def test_transpose(r, c):
    x = rand(r, c)
    np.testing.assert_array_equal(np.asarray(transpose(jnp.asarray(x), tile=32)),
                                  x.T)


def test_paper_n4096():
    """The paper's exact FFT size (N = 4096, the 32 KiB line)."""
    xr, xi = rand(2, 4096), rand(2, 4096)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=2)
    assert_close(got, ref.fft_ref(xr, xi, axis=1), tol=5e-4)


# ---------------------------------------------------------------------------
# Batched multi-scene dispatch + mixed-radix three-factor decompositions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("n", [512, 4096, 8192])
def test_batched_fused_pipeline_vs_ref(B, n):
    """The batched fused dispatch (FFT * H * IFFT over (B, L, n)) matches
    the unfused per-scene jnp.fft reference at the seed tolerance."""
    lines = 4
    xr, xi = rand(B, lines, n), rand(B, lines, n)
    hr, hi = rand(n), rand(n)
    got = ops.fused_fft_mult_ifft_rows(
        jnp.asarray(xr), jnp.asarray(xi), jnp.asarray(hr), jnp.asarray(hi),
        block=2)
    assert got[0].shape == (B, lines, n)
    want = ref.spectral_ref(xr, xi, axis=-1, fwd=True, inv=True, hr=hr, hi=hi)
    assert_close(got, want, tol=5e-4)


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("n", [512, 4096, 8192])
def test_batched_fft_rows_and_cols(B, n):
    lines = 4
    xr, xi = rand(B, lines, n), rand(B, lines, n)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=2)
    assert_close(got, ref.fft_ref(xr, xi, axis=-1), tol=5e-4)
    xr, xi = rand(B, n, lines), rand(B, n, lines)
    got = ops.fft_cols(jnp.asarray(xr), jnp.asarray(xi), block=2)
    assert_close(got, ref.fft_ref(xr, xi, axis=-2), tol=5e-4)


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("n1,n2,n3", [(8, 8, 8), (16, 8, 4), (32, 16, 16)])
def test_three_factor_explicit(B, n1, n2, n3):
    n = n1 * n2 * n3
    xr, xi = rand(B, 4, n), rand(B, 4, n)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), n1=n1, n2=n2, n3=n3,
                       block=2)
    assert_close(got, ref.fft_ref(xr, xi, axis=-1), tol=5e-4)


def test_three_factor_default_32768():
    """Lengths past 128*128 decompose to three factors instead of erroring."""
    from repro.kernels.fft4step import default_factorization
    fs = default_factorization(32768)
    assert len(fs) == 3 and all(f <= 128 for f in fs)
    xr, xi = rand(2, 32768), rand(2, 32768)
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=2)
    assert_close(got, ref.fft_ref(xr, xi, axis=1), tol=1e-3)


def test_batched_outer_and_full_filters():
    B, lines, n = 2, 4, 128
    xr, xi = rand(B, lines, n), rand(B, lines, n)
    u, v = rand(lines, 2), rand(n, 2)
    got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi),
                          u=jnp.asarray(u), v=jnp.asarray(v),
                          fwd=True, inv=True, axis=1, block=2,
                          filter_mode="outer")
    want = ref.spectral_ref(xr, xi, axis=-1, fwd=True, inv=True, u=u, v=v)
    assert_close(got, want, tol=5e-4)
    hr, hi = rand(lines, n), rand(lines, n)
    got = ops.spectral_op(jnp.asarray(xr), jnp.asarray(xi),
                          hr=jnp.asarray(hr), hi=jnp.asarray(hi),
                          fwd=True, inv=True, axis=1, block=2,
                          filter_mode="full")
    want = ref.spectral_ref(xr, xi, axis=-1, fwd=True, inv=True, hr=hr, hi=hi)
    assert_close(got, want, tol=5e-4)


def test_unbatched_equals_b1():
    """The 2-D public API is exactly the B=1 slice of the batched path."""
    xr, xi = rand(4, 256), rand(4, 256)
    a = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=2)
    b = ops.fft_rows(jnp.asarray(xr)[None], jnp.asarray(xi)[None], block=2)
    assert a[0].shape == (4, 256) and b[0].shape == (1, 4, 256)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0][0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1][0]))


def test_batched_transpose():
    x = rand(3, 64, 64)
    got = np.asarray(transpose(jnp.asarray(x), tile=32))
    np.testing.assert_array_equal(got, np.swapaxes(x, -1, -2))


# ---------------------------------------------------------------------------
# Property tests (hypothesis)
# ---------------------------------------------------------------------------

shapes = st.sampled_from([(2, 16), (4, 64), (2, 256)])


@settings(max_examples=20, deadline=None)
@given(shape=shapes, seed=st.integers(0, 2**31 - 1),
       a=st.floats(-3, 3), b=st.floats(-3, 3))
def test_linearity(shape, seed, a, b):
    r = np.random.default_rng(seed)
    x = r.standard_normal(shape).astype(np.float32)
    y = r.standard_normal(shape).astype(np.float32)
    z = np.zeros(shape, np.float32)
    fx = ops.fft_rows(jnp.asarray(x), jnp.asarray(z), block=2)
    fy = ops.fft_rows(jnp.asarray(y), jnp.asarray(z), block=2)
    fxy = ops.fft_rows(jnp.asarray(a * x + b * y), jnp.asarray(z), block=2)
    want = (a * fx[0] + b * fy[0], a * fx[1] + b * fy[1])
    assert_close(fxy, want, tol=1e-3)


@settings(max_examples=20, deadline=None)
@given(shape=shapes, seed=st.integers(0, 2**31 - 1))
def test_parseval(shape, seed):
    r = np.random.default_rng(seed)
    xr = r.standard_normal(shape).astype(np.float32)
    xi = r.standard_normal(shape).astype(np.float32)
    fr, fi = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=2)
    e_t = np.sum(xr**2 + xi**2)
    e_f = float(jnp.sum(fr**2 + fi**2)) / shape[1]
    np.testing.assert_allclose(e_f, e_t, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(shape=shapes, seed=st.integers(0, 2**31 - 1))
def test_ifft_inverts_fft(shape, seed):
    r = np.random.default_rng(seed)
    xr = r.standard_normal(shape).astype(np.float32)
    xi = r.standard_normal(shape).astype(np.float32)
    fr, fi = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=2)
    br, bi = ops.ifft_rows(fr, fi, block=2)
    assert_close((br, bi), (xr, xi), tol=1e-3)


@settings(max_examples=40, deadline=None)
@given(mag=st.floats(-60, 60), axis=st.sampled_from([0, 1]),
       seed=st.integers(0, 2**31 - 1))
def test_bs16_codec_round_trip(mag, axis, seed):
    """The bs16 exponent codec: extract -> remove -> apply is the EXACT
    identity (power-of-two scaling never rounds a normal float), and the
    f16-quantized round trip stays within the half-float mantissa bound
    (2^-10 of each line's amax), for line magnitudes across 2^-60..2^60
    — the dynamic range the per-line exponents exist to absorb."""
    from repro.kernels.fft4step import apply_exponents, line_exponents, \
        remove_exponents
    r = np.random.default_rng(seed)
    shape = (4, 32)
    scale = np.float32(2.0) ** np.float32(mag)
    xr = (r.standard_normal(shape) * scale).astype(np.float32)
    xi = (r.standard_normal(shape) * scale).astype(np.float32)
    exp = line_exponents(jnp.asarray(xr), jnp.asarray(xi), axis)
    sr, si = remove_exponents(jnp.asarray(xr), jnp.asarray(xi), exp)
    # scaled magnitudes land in [0, 1]: representable in f16 verbatim
    assert float(jnp.max(jnp.abs(sr))) <= 1.0
    assert float(jnp.max(jnp.abs(si))) <= 1.0
    rr, ri = apply_exponents(sr, si, exp)
    np.testing.assert_array_equal(np.asarray(rr), xr)
    np.testing.assert_array_equal(np.asarray(ri), xi)
    # quantizing the scaled mantissas to f16 bounds the error per LINE
    qr = np.asarray(sr).astype(np.float16).astype(np.float32)
    qi = np.asarray(si).astype(np.float16).astype(np.float32)
    qrr, qri = apply_exponents(jnp.asarray(qr), jnp.asarray(qi), exp)
    red = 1 if axis == 1 else 0
    amax = np.maximum(np.abs(xr).max(axis=red, keepdims=True),
                      np.abs(xi).max(axis=red, keepdims=True))
    bound = amax * 2.0 ** -10
    assert np.all(np.abs(np.asarray(qrr) - xr) <= bound)
    assert np.all(np.abs(np.asarray(qri) - xi) <= bound)


@settings(max_examples=15, deadline=None)
@given(shape=shapes, seed=st.integers(0, 2**31 - 1))
def test_fused_equals_composed(shape, seed):
    """The paper's core claim: one fused dispatch == the 3-dispatch chain."""
    r = np.random.default_rng(seed)
    lines, n = shape
    xr = r.standard_normal(shape).astype(np.float32)
    xi = r.standard_normal(shape).astype(np.float32)
    hr = r.standard_normal(n).astype(np.float32)
    hi = r.standard_normal(n).astype(np.float32)
    fused = ops.fused_fft_mult_ifft_rows(
        jnp.asarray(xr), jnp.asarray(xi), jnp.asarray(hr), jnp.asarray(hi),
        block=2)
    fr, fi = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), block=2)
    mr, mi = fr * hr - fi * hi, fr * hi + fi * hr
    want = ops.ifft_rows(mr, mi, block=2)
    assert_close(fused, (np.asarray(want[0]), np.asarray(want[1])), tol=1e-3)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_auto_interpret_by_backend(monkeypatch, backend, interpret):
    """Interpret mode only on the CPU, compiled on a TPU, and any other
    backend is an error rather than a silent interpreter fallback."""
    from repro.kernels import fft4step
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert fft4step.auto_interpret(True) is True
    assert fft4step.auto_interpret(False) is False
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            fft4step.auto_interpret(None)
    else:
        assert fft4step.auto_interpret(None) is interpret
