"""Jit'd public wrappers around the fused spectral Pallas kernel.

All functions take/return split re/im float32 arrays. `interpret=None`
auto-selects interpret mode off-TPU (this container is CPU-only; on a real
TPU fleet the same code lowers to Mosaic).

Batching: every wrapper accepts either one scene — (lines, N) rows layout /
(N, lines) cols layout — or a batch of scenes with a leading batch
dimension, (B, lines, N) / (B, N, lines). Batched inputs run as ONE fused
dispatch with the Pallas grid spanning B x line-blocks (see fft4step.py);
2-D inputs are transparently treated as B=1 and squeezed on return. Filter
arguments are always unbatched (scenes share the SceneConfig filters).

The wrappers handle line-count padding so callers never worry about the
block size; the kernel itself assumes divisibility.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fft4step import (
    FILTER_FULL,
    FILTER_NONE,
    FILTER_OUTER,
    FILTER_SHARED,
    FILTER_SHARED_OUTER,
    RESIDENT_VMEM,
    MegaSpec,
    SegmentSpec,
    SpectralSpec,
    apply_exponents,
    auto_interpret,
    build_mega_call,
    build_spectral_call,
    default_line_block as _line_block,
    line_exponents,
    remove_exponents,
    resolve_precision,
)

# the one backend check every kernel wrapper shares (fft4step.auto_interpret)
_auto_interpret = auto_interpret


def _pad_lines(x, axis, mult):
    lines = x.shape[axis]
    pad = (-lines) % mult
    if pad == 0:
        return x, lines
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), lines


@functools.partial(
    jax.jit,
    static_argnames=(
        "axis", "fwd", "inv", "filter_mode", "block", "fft_impl",
        "karatsuba", "precision", "compute_dtype", "interpret", "n1", "n2",
        "n3", "batch_block",
    ),
)
def spectral_op(
    xr,
    xi,
    hr=None,
    hi=None,
    u=None,
    v=None,
    *,
    axis: int = 1,
    fwd: bool = True,
    inv: bool = True,
    filter_mode: str = FILTER_NONE,
    block: Optional[int] = None,
    fft_impl: str = "matmul",
    karatsuba: bool = False,
    precision: Optional[str] = None,
    compute_dtype: Optional[str] = None,
    interpret: Optional[bool] = None,
    n1: Optional[int] = None,
    n2: Optional[int] = None,
    n3: Optional[int] = None,
    batch_block: Optional[int] = None,
):
    """One fused dispatch: [FFT] -> [filter multiply] -> [IFFT] along `axis`.

    x: (lines, N) when axis=1, (N, lines) when axis=0 — or a batch of
    scenes, (B, lines, N) / (B, N, lines), fused into the same single
    dispatch (`axis` always names the scene axis, batch excluded).
    filter args by mode (unbatched; shared across any batch):
      shared: hr/hi (N,)       — e.g. the range matched filter
      full:   hr/hi one scene's shape
      outer:  u (lines,) or (lines, K), v (N,) or (N, K) —
              filter = exp(i * sum_k u[line,k] * v[sample,k])
    n1/n2/n3: optional mixed-radix factorization override (n = n1*n2[*n3],
    every factor <= 128, any radix); default per
    fft4step.default_factorization (any length with such a split: 6144 =
    48*128; powers of two keep the ~sqrt split, 4096 = 64*64).
    precision: matmul-operand Precision policy name (fft4step.PRECISIONS:
    f32 | bf16 | f16 | bs16 block-scaled f16). `compute_dtype` is the
    deprecated pre-policy spelling of the same knob.
    """
    precision = resolve_precision(precision or compute_dtype).name
    block = block or _line_block()
    batched = xr.ndim == 3
    if not batched:
        xr = xr[None]
        xi = xi[None]
    b = xr.shape[0]
    line_axis = 1 if axis == 1 else 2
    n = xr.shape[axis + 1]
    xr, lines = _pad_lines(xr, line_axis, block)
    xi, _ = _pad_lines(xi, line_axis, block)

    outer_rank = 1
    if filter_mode in (FILTER_OUTER, FILTER_SHARED_OUTER):
        u = u.reshape(u.shape[0], -1)
        v = v.reshape(v.shape[0], -1)
        outer_rank = u.shape[1]

    spec = SpectralSpec(
        n=n, fwd=fwd, inv=inv, filter_mode=filter_mode, axis=axis,
        block=block, batch_block=batch_block, fft_impl=fft_impl,
        karatsuba=karatsuba, precision=precision, n1=n1, n2=n2,
        n3=n3, outer_rank=outer_rank,
    )
    call = build_spectral_call(spec, xr.shape[line_axis], batch=b,
                               interpret=_auto_interpret(interpret))

    filt_line_axis = 0 if axis == 1 else 1   # filters stay 2-D
    filter_args = []
    if filter_mode == FILTER_SHARED:
        fshape = (1, n) if axis == 1 else (n, 1)
        filter_args = [hr.reshape(fshape), hi.reshape(fshape)]
    elif filter_mode == FILTER_FULL:
        hr, _ = _pad_lines(hr, filt_line_axis, block)
        hi, _ = _pad_lines(hi, filt_line_axis, block)
        filter_args = [hr, hi]
    elif filter_mode in (FILTER_OUTER, FILTER_SHARED_OUTER):
        pad = (-lines) % block
        u = jnp.pad(u, ((0, pad), (0, 0)))      # (lines_padded, K)
        if axis == 1:
            filter_args = [u, v.T]              # (L, K), (K, N)
        else:
            filter_args = [u.T, v]              # (K, L), (N, K)
        if filter_mode == FILTER_SHARED_OUTER:
            fshape = (1, n) if axis == 1 else (n, 1)
            filter_args = [hr.reshape(fshape), hi.reshape(fshape)] + filter_args

    yr, yi = call(xr, xi, *filter_args)
    if line_axis == 1:
        yr, yi = yr[:, :lines], yi[:, :lines]
    else:
        yr, yi = yr[:, :, :lines], yi[:, :, :lines]
    if not batched:
        return yr[0], yi[0]
    return yr, yi


@functools.partial(
    jax.jit,
    static_argnames=(
        "segments", "residency", "batch_block", "phase_block",
        "buffer_depth", "fft_impl",
        "karatsuba", "precision", "interpret", "n1", "n2", "n3",
        "return_exp",
    ),
)
def mega_spectral_op(
    xr,
    xi,
    *filter_args,
    segments,
    residency: str = RESIDENT_VMEM,
    batch_block: Optional[int] = None,
    phase_block: Optional[int] = None,
    buffer_depth: int = 2,
    fft_impl: str = "matmul",
    karatsuba: bool = False,
    precision: Optional[str] = None,
    interpret: Optional[bool] = None,
    n1: Optional[int] = None,
    n2: Optional[int] = None,
    n3: Optional[int] = None,
    exp_in=None,
    return_exp: bool = False,
):
    """The single-dispatch 2-D megakernel: a whole multi-axis spectral
    pipeline — `fft? mul* ifft?` segments with in-kernel corner turns
    between them — as ONE fused dispatch.

    x: one scene (na, nr) or a batch (B, na, nr), split re/im float32 in
    scene layout (azimuth rows x range samples). ``segments`` is a static
    tuple of ``(axis, fwd, inv, filter_mode)`` records in execution order
    (axis 1 transforms the range axis, 0 the azimuth axis); a record may
    extend to ``(axis, fwd, inv, filter_mode, n1, n2, n3, karatsuba)`` to
    pin THAT segment's factorization and complex-product algorithm — the
    per-segment decisions a tuned ``repro.tuning.Schedule`` carries
    (``None`` fields defer to the global knobs below).
    ``filter_args`` follow in segment order, each segment contributing its
    mode's payload in SCENE coordinates (n = transformed-axis length,
    lines = the other axis):

      shared:       hr (n,), hi (n,)
      full:         hr (na, nr), hi (na, nr)
      outer:        u (lines,) or (lines, K); v (n,) or (n, K)
      shared_outer: hr, hi, u, v

    residency 'vmem' holds the whole (Bb, na, nr) slab on-chip (zero HBM
    intermediates — the paper's single-dispatch claim); 'staged' runs a
    phase-split grid with an HBM scratch corner-turn intermediate and
    ``buffer_depth``-slot DMA buffering (large scenes; depth 1 disables
    the copy/compute overlap). f32 results are bit-identical between the
    modes and to the equivalent per-axis dispatch chain.
    n1/n2/n3 override the RANGE-axis factorization (the azimuth axis uses
    the default split), matching ``compile_plan``'s ``fft_kw`` convention.

    ``exp_in`` / ``return_exp`` (block-scaled precisions only) chain the
    carried per-line exponents ACROSS megakernel dispatches — the sharded
    lowering's corner-turn contract. With ``return_exp=True`` the result
    comes back scaled, as ``(yr, yi, exp)``: ``exp`` holds the per-line
    exponents along the LAST segment's free axis — exactly what the next
    dispatch's prologue would extract — and the scaled slab is what rides
    the all_to_all wire. Passing that ``exp`` as the next call's
    ``exp_in`` (all_gathered to full length when the free axis is
    re-sharded) restores the values exactly, power-of-two scaling being
    bit-exact, so a chain of dispatches matches one fused dispatch bit
    for bit.
    """
    prec = resolve_precision(precision)
    precision = prec.name
    if (exp_in is not None or return_exp) and not prec.block_scaled:
        raise ValueError(
            "exp_in/return_exp carry block exponents and require a "
            f"block-scaled precision, got {precision!r}")
    batched = xr.ndim == 3
    if not batched:
        xr = xr[None]
        xi = xi[None]
    b, na, nr = xr.shape
    if exp_in is not None:
        # the previous dispatch's carried exponents: fold them back in
        # (exact) before the prologue re-extracts along this dispatch's
        # first free axis
        xr, xi = apply_exponents(xr, xi, exp_in)

    segs = []
    args = list(filter_args)
    prepared = []
    ai = 0
    for seg_rec in segments:
        if len(seg_rec) == 4:
            (axis, fwd, inv, fmode), seg_kw = seg_rec, {}
        elif len(seg_rec) == 8:
            axis, fwd, inv, fmode = seg_rec[:4]
            seg_kw = dict(zip(("n1", "n2", "n3", "karatsuba"), seg_rec[4:]))
        else:
            raise ValueError(
                f"segment record must have 4 fields (axis, fwd, inv, "
                f"filter_mode) or 8 (+ n1, n2, n3, karatsuba), got "
                f"{len(seg_rec)}")
        n = nr if axis == 1 else na
        rank = 1
        if fmode in (FILTER_SHARED, FILTER_FULL, FILTER_SHARED_OUTER):
            hr, hi = args[ai], args[ai + 1]
            ai += 2
            if fmode == FILTER_FULL:
                prepared += [hr, hi]
            else:
                shape = (1, n) if axis == 1 else (n, 1)
                prepared += [hr.reshape(shape), hi.reshape(shape)]
        if fmode in (FILTER_OUTER, FILTER_SHARED_OUTER):
            u, v = args[ai], args[ai + 1]
            ai += 2
            u = u.reshape(u.shape[0], -1)
            v = v.reshape(v.shape[0], -1)
            rank = u.shape[1]
            prepared += ([u, v.T] if axis == 1 else [u.T, v])
        segs.append(SegmentSpec(axis=axis, fwd=fwd, inv=inv,
                                filter_mode=fmode, outer_rank=rank,
                                **seg_kw))
    if ai != len(args):
        raise ValueError(
            f"got {len(args)} filter arrays but segments consume {ai}")

    spec = MegaSpec(
        na=na, nr=nr, segments=tuple(segs), residency=residency,
        batch_block=batch_block, phase_block=phase_block or _line_block(),
        buffer_depth=buffer_depth, n1=n1, n2=n2,
        n3=n3, fft_impl=fft_impl, karatsuba=karatsuba, precision=precision)
    call = build_mega_call(spec, batch=b,
                           interpret=_auto_interpret(interpret))
    yr, yi = call(xr, xi, *prepared)
    if return_exp:
        # hand the carry to the NEXT dispatch: re-extract along the last
        # segment's free axis (bit-identical to what its prologue would
        # compute) and return the slab scaled
        exp = line_exponents(yr, yi, segs[-1].axis)
        yr, yi = remove_exponents(yr, yi, exp)
        if not batched:
            return yr[0], yi[0], exp[0]
        return yr, yi, exp
    if not batched:
        return yr[0], yi[0]
    return yr, yi


# ---- Convenience entry points (named for the SAR pipeline steps) ----------

def fft_rows(xr, xi, **kw):
    """Batched forward FFT along the last axis of (B, N)."""
    return spectral_op(xr, xi, fwd=True, inv=False, axis=1, **kw)


def ifft_rows(xr, xi, **kw):
    return spectral_op(xr, xi, fwd=False, inv=True, axis=1, **kw)


def fft_cols(xr, xi, **kw):
    """Forward FFT along axis 0 of (N, C) — transpose-free column pipeline."""
    return spectral_op(xr, xi, fwd=True, inv=False, axis=0, **kw)


def ifft_cols(xr, xi, **kw):
    return spectral_op(xr, xi, fwd=False, inv=True, axis=0, **kw)


def fused_fft_mult_ifft_rows(xr, xi, hr, hi, **kw):
    """The paper's fused range-compression dispatch: FFT · H · IFFT per line."""
    return spectral_op(xr, xi, hr=hr, hi=hi, fwd=True, inv=True, axis=1,
                       filter_mode=FILTER_SHARED, **kw)


def fused_mult_ifft_cols(xr, xi, hr, hi, **kw):
    """The paper's fused azimuth-compression dispatch: H · IFFT per column
    (data already in the azimuth frequency domain). hr/hi is the full 2-D
    azimuth filter H_a(f_a, R0)."""
    return spectral_op(xr, xi, hr=hr, hi=hi, fwd=False, inv=True, axis=0,
                       filter_mode=FILTER_FULL, **kw)


def fused_rcmc_rows(xr, xi, shift, freqs, **kw):
    """Beyond-paper: exact RCMC as one fused dispatch per azimuth-frequency row:
    FFT -> exp(i * shift[row] * freqs[col]) -> IFFT (Fourier shift theorem),
    with the rank-1 phase synthesized in VMEM (FILTER_OUTER)."""
    return spectral_op(xr, xi, u=shift, v=freqs, fwd=True, inv=True, axis=1,
                       filter_mode=FILTER_OUTER, **kw)


def fused_mult_ifft_cols_outer(xr, xi, u, v, **kw):
    """Azimuth compression with on-the-fly rank-1 phase: H = exp(i u[col] v[row])
    — u is the per-column (range gate) 1/Ka term, v the per-row -pi f_a^2."""
    return spectral_op(xr, xi, u=u, v=v, fwd=False, inv=True, axis=0,
                       filter_mode=FILTER_OUTER, **kw)


def fused_rc_rcmc_rows(xr, xi, hr, hi, u, v, **kw):
    """Beyond-paper 3-dispatch RDA, middle dispatch: range compression AND
    exact RCMC in one pass (data already in the azimuth-frequency domain):
    FFT -> H_r[col] * exp(i shift[row] * freqs[col]) -> IFFT."""
    return spectral_op(xr, xi, hr=hr, hi=hi, u=u, v=v, fwd=True, inv=True,
                       axis=1, filter_mode=FILTER_SHARED_OUTER, **kw)
