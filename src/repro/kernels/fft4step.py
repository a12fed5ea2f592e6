"""Single-dispatch fused spectral-pipeline Pallas kernel (the paper's contribution).

The paper fuses FFT -> matched-filter multiply -> IFFT into one Metal dispatch,
holding a 4096-point complex line in 32 KiB of threadgroup memory, and feeds
Apple's 8x8 simdgroup MMA with a radix-8 DFT butterfly.

TPU adaptation (see DESIGN.md SS2):
  * on-chip tier   : 32 KiB threadgroup memory  ->  VMEM (128 MiB on v5e,
    limits sized per kernel by :func:`compiler_params`). We block a
    *batch of lines* (row pipeline) or a whole (N x L) column slab (column
    pipeline) per grid step, instead of one line per threadgroup.
  * matrix unit    : 8x8 simdgroup MMA -> 128x128 MXU. The radix-8 butterfly
    becomes a *four-step FFT*: N = n1*n2, each stage a dense matmul against a
    DFT matrix (n1, n2 <= 128), twiddle as a pointwise multiply. Complex
    arithmetic is split re/im (4 real matmuls, or 3 with Karatsuba).
  * IFFT           : conj-FFT-conj with the 1/N scale folded into the final
    store — identical to the paper's SSII-C trick.
  * the paper's in-place constraint (Stockham needs 2x buffers > 32 KiB) does
    not bind in VMEM; we keep the numerically-identical out-of-place stages
    inside the kernel and spend the slack on line batching.

A 'stockham' VPU implementation (radix-4/radix-2, no matmuls) is provided as
the scalar baseline for the paper's Table I comparison.

Batched multi-scene dispatch (beyond-paper)
-------------------------------------------
Every kernel takes a leading batch dimension: x is (B, lines, N) for the
rows pipeline and (B, N, lines) for the columns pipeline. The Pallas grid
spans ``batch-blocks x line-blocks`` and each grid step holds a
(Bb, L, N) slab — the SAME line-block of Bb scenes — which the transform
folds into one (Bb*L, N) line batch. Scenes therefore share one dispatch,
one set of broadcast DFT-constant blocks per step, and larger (better
MXU-shaped) matmuls; none of that happens with a Python-level vmap, which
re-issues the whole dispatch per scene. Filters are batch-shared (one
(lines, N) filter / (N,) vector / rank-K phase for all B scenes), matching
multi-scene SAR where every scene uses the same SceneConfig. The unbatched
public API in kernels/ops.py is the B=1 special case (2-D inputs are
expanded and squeezed transparently).

Mixed-radix factorization rules
-------------------------------
``SpectralSpec.factors()`` returns a two- OR three-factor decomposition
``n = n1*n2[*n3]`` with every factor <= 128 (the MXU edge); a DFT stage
is a dense matmul, so any radix up to 128 runs, not only powers of two:

  * powers of two, n <= 16384: the ~sqrt two-factor split (n1 >= n2),
    e.g. 4096 = 64*64, 8192 = 128*64, 512 = 32*16;
  * powers of two, 16384 < n <= 2^21: a three-factor split, e.g.
    32768 = 32*32*32 — the four-step formulation applies recursively
    (stage-A matmul, twiddle, then a four-step FFT of the remaining
    length), so lengths beyond 128*128 still map onto dense MXU matmuls;
  * other lengths (a range line of 5616 samples, or the 6144-point
    transform that holds it): two factors up to 128*128, three past it.
    A split whose innermost factor is 128 and whose others are multiples
    of 8 comes first (6144 = 48*128): every reshape and leading-dim swap
    of the transform then stays on whole (8, 128) tiles. Else a split
    of multiples of 8 (384 = 24*16), else the most even one
    (96 = 12*8, 5616 = 78*72; Mosaic pads the partial tiles).
  * a length with no such split (2*131, or past 128^3) raises a
    ValueError naming it; nothing pads to a longer transform. The
    Stockham baseline and the float16-operand tiers (f16, bs16) run
    powers of two only and refuse other lengths (:func:`check_length`).

Explicit ``n1``/``n2``/``n3`` override the default (the repro.tuning
subsystem sweeps them per (B, n) together with ``block``, ``karatsuba``
and ``precision``, and caches the fastest config per device fingerprint;
``build_spectral_call`` also accepts a whole ``repro.tuning.KernelConfig``
via its ``config`` parameter).

Everything is validated in interpret mode against kernels/ref.py (pure jnp).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Filter (pointwise multiply) modes for the fused pipeline.
FILTER_NONE = "none"      # no multiply (pure FFT / pure IFFT dispatch)
FILTER_SHARED = "shared"  # one N-vector shared by every line (range matched filter)
FILTER_FULL = "full"      # full 2-D filter, same shape as the scene block
FILTER_OUTER = "outer"    # on-the-fly rank-K phase synthesis
                          # exp(i * sum_k u[line,k] * v[sample,k])
                          # (covers RCMC phase ramps and azimuth compression —
                          #  beyond-paper bandwidth optimization: O(N+L) filter
                          #  I/O instead of O(N*L))
FILTER_SHARED_OUTER = "shared_outer"  # H[sample] * exp(i sum_k u v): range
                          # matched filter and RCMC shift in ONE dispatch
                          # (the 3-dispatch RDA; beyond-paper)


MAX_FACTOR = 128  # MXU edge: every DFT matmul factor must be <= 128
SUBLANES = 8      # rows of a TPU vreg tile: a compiled split's factor unit

# Residency modes of the single-dispatch 2-D megakernel (build_mega_call).
RESIDENT_VMEM = "vmem"      # whole (Bb, na, nr) slab on-chip per grid step
RESIDENT_STAGED = "staged"  # phase-split grid + HBM scratch, DMA-staged


def auto_interpret(interpret: Optional[bool]) -> bool:
    """Resolve the tri-state ``interpret`` flag every kernel wrapper takes.

    None compiles the kernels with Mosaic on a TPU and runs them in the
    Pallas interpreter on the host CPU (the test path). Any other backend
    raises: a GPU or plugin backend must never quietly fall back to the
    interpreter and report its times as kernel times."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas lowering for backend {backend!r}: kernels compile for "
        "'tpu' and are interpreted only on 'cpu'")


def device_kind() -> str:
    """``device_kind`` of the first JAX device — the key of the per-device
    table in ``repro.tuning.cost`` that sizes VMEM limits and line blocks."""
    return jax.devices()[0].device_kind


def _device_spec():
    from repro.tuning.cost import device_spec  # tuning imports this module
    return device_spec(device_kind())


def default_line_block() -> int:
    """Lines per rows-dispatch grid step and per staged-megakernel phase
    step when none is pinned: the device table's ``line_block``."""
    return _device_spec().line_block


def check_precision(precision, interpret: bool) -> None:
    """Refuse a compiled kernel whose matmul operand dtype the device's
    matrix unit does not take (float16 on TPU v5e: Mosaic cannot even
    pack f32 into f16 there). Interpret mode emulates every dtype."""
    prec = resolve_precision(precision)
    if interpret or prec.dtype != "float16":
        return
    spec = _device_spec()
    if not spec.f16_operands:
        raise ValueError(
            f"precision {prec.name!r} needs float16 matmul operands, which "
            f"{spec.kind} does not support; use one of "
            f"{sorted(p for p, v in PRECISIONS.items() if v.dtype != 'float16')}")


def check_length(spec) -> None:
    """Refuse a transform whose length the chosen implementation cannot
    run: the Stockham baseline (radix 4 and 2) and the float16-operand
    tiers (f16, bs16) run powers of two only. The DFT-as-matmul kernels
    at f32 and bf16 take any length :func:`default_factorization` can
    split."""
    if not (spec.fwd or spec.inv) or _is_pow2(spec.n):
        return
    if spec.fft_impl == "stockham":
        raise ValueError(
            f"fft_impl 'stockham' runs power-of-two lengths only, got "
            f"n={spec.n}; use fft_impl 'matmul'")
    if PRECISIONS[spec.precision].dtype == "float16":
        raise ValueError(
            f"precision {spec.precision!r} runs power-of-two lengths only, "
            f"got n={spec.n}; use 'f32' or 'bf16'")


_DEFAULT_SCOPED_VMEM = 16 * 2**20     # Mosaic's limit when none is set


def compiler_params(vmem_estimate: int, interpret: bool, **kw):
    """Mosaic compiler parameters with a scoped-VMEM limit sized from the
    kernel's footprint estimate: 25% headroom over it, never below the
    compiler's own default and never above what the device has. A kernel
    whose estimate alone exceeds the device raises here, not in Mosaic."""
    if interpret:
        return None
    spec = _device_spec()
    if vmem_estimate > spec.vmem_bytes:
        raise ValueError(
            f"kernel needs ~{vmem_estimate / 2**20:.1f} MiB of VMEM; "
            f"{spec.kind} has {spec.vmem_bytes / 2**20:.0f} MiB")
    limit = min(spec.vmem_bytes,
                max(_DEFAULT_SCOPED_VMEM, int(vmem_estimate * 1.25)))
    return pltpu.CompilerParams(vmem_limit_bytes=limit, **kw)


# ---------------------------------------------------------------------------
# Precision policy
# ---------------------------------------------------------------------------
#
# Matmul-operand precision of the in-kernel DFT stages ("Range, Not
# Precision", arXiv 2605.28451: FFT inputs are range-limited, so narrow
# floats with a shared block exponent keep SAR image quality while doubling
# matrix-unit throughput). Accumulation is always float32
# (preferred_element_type); only the dot operands are narrowed.
#
#   f32   exact float32 operands (default)
#   bf16  bfloat16 operands — wide exponent, 8-bit mantissa
#   f16   float16 operands — 11-bit mantissa but narrow exponent (can
#         overflow past |x| ~ 6.5e4; prefer bs16)
#   bs16  block-scaled float16: the kernel prologue extracts one power-of-two
#         exponent PER LINE along the segment's free axis (scale division is
#         exact in f32), runs the fused pipeline on the scaled data with f16
#         operands, and the epilogue re-applies the exponents at the final
#         store. Combines f16's mantissa with an unbounded effective exponent
#         range. Per-line granularity makes the policy invariant to every
#         grid blocking (line blocks, batch blocks, staged phase blocks,
#         device sharding): any block of lines sees exactly the exponents its
#         lines would get in any other partitioning, so bs16 results are
#         bit-identical across the per-axis, megakernel, and sharded routes.
#         Between segments the megakernels RE-BLOCK: apply the carried
#         exponents (exact), re-extract along the new segment's free axis,
#         rescale — matching the per-dispatch extraction of the multi-
#         dispatch pipeline bit for bit.

@dataclasses.dataclass(frozen=True)
class Precision:
    """One matmul-operand precision policy for the fused kernel."""

    name: str
    dtype: str            # operand dtype the DFT matmuls are cast to
    block_scaled: bool    # per-line exponent extraction in prologue/epilogue

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


PRECISIONS: dict[str, Precision] = {
    "f32": Precision("f32", "float32", False),
    "bf16": Precision("bf16", "bfloat16", False),
    "f16": Precision("f16", "float16", False),
    "bs16": Precision("bs16", "float16", True),
}


def resolve_precision(p) -> Precision:
    """Accepts a Precision, a policy name, or None (-> f32)."""
    if p is None:
        return PRECISIONS["f32"]
    if isinstance(p, Precision):
        return p
    try:
        return PRECISIONS[p]
    except KeyError:
        raise ValueError(
            f"unknown precision {p!r}; one of {sorted(PRECISIONS)}") from None


def _is_pow2(n: int) -> bool:
    return n >= 1 and not n & (n - 1)


def _pow2_factorization(n: int) -> tuple[int, ...]:
    """The power-of-two rule: the ~sqrt split, n1 >= n2, then three
    factors f1 >= f2 >= f3 past 128*128."""
    p = n.bit_length() - 1
    if n <= MAX_FACTOR * MAX_FACTOR:
        n1 = 1 << ((p + 1) // 2)
        return n1, n // n1
    p1 = (p + 2) // 3
    p2 = (p - p1 + 1) // 2
    return 1 << p1, 1 << p2, 1 << (p - p1 - p2)


def splits(n: int, k: int) -> list[tuple[int, ...]]:
    """Every ordered split of n into k factors, each between 1 and
    MAX_FACTOR (n = f1 * ... * fk)."""
    if k == 1:
        return [(n,)] if 1 <= n <= MAX_FACTOR else []
    return [(f,) + rest for f in range(1, MAX_FACTOR + 1) if n % f == 0
            for rest in splits(n // f, k - 1)]


def _tiled(fs: Sequence[int]) -> bool:
    """Every factor a multiple of the 8-row sublane tile: each reshape
    and leading-dim swap of the four-step recursion on an (n, lines) slab
    then stays on whole (8, 128) tiles. Other splits compile for a v5e
    too (5616 = 78 * 72), with their partial tiles padded."""
    return all(f % SUBLANES == 0 for f in fs)


def _preference(fs: tuple) -> tuple:
    """Sort key of a non-power-of-two split: the 128-point factor innermost
    with the rest on whole sublane tiles (6144 = 48 * 128), then any tiled
    split, then the most even split (least sum), larger factors first."""
    lane_whole = fs[-1] == MAX_FACTOR and _tiled(fs[:-1])
    return (not lane_whole, not _tiled(fs), sum(fs), tuple(-f for f in fs))


def default_factorization(n: int) -> tuple[int, ...]:
    """Mixed-radix split of n into 2 factors (3 past 128*128), each <= 128.

    Powers of two:  the ~sqrt two-factor split with n1 >= n2 (the paper's
                    regime: 4096 = 64*64; plus 8192 = 128*64, 512 = 32*16),
                    then three factors f1 >= f2 >= f3 (32768 = 32*32*32).
    Other lengths:  a split whose innermost factor is 128 and whose others
                    are multiples of 8 where one exists (6144 = 48*128),
                    else one of multiples of 8 (384 = 24*16), else the most
                    even split (96 = 12*8, 5616 = 78*72).

    A length with no split into factors <= 128 (2*131, or past 128^3)
    raises a ValueError naming it; nothing pads to a longer transform.
    """
    if n < 1:
        raise ValueError(f"FFT length must be positive, got {n}")
    if n > MAX_FACTOR ** 3:
        raise ValueError(
            f"n={n} exceeds the three-factor limit {MAX_FACTOR ** 3}")
    if _is_pow2(n):
        return _pow2_factorization(n)
    fs = splits(n, 2 if n <= MAX_FACTOR * MAX_FACTOR else 3)
    if not fs:
        raise ValueError(
            f"FFT length {n} has no split into factors <= {MAX_FACTOR}")
    return min(fs, key=_preference)


def resolve_factors(n: int, n1: Optional[int] = None,
                    n2: Optional[int] = None,
                    n3: Optional[int] = None) -> tuple[int, ...]:
    """The split a transform of length n runs: the pinned ``n1/n2/n3``
    (``n2`` defaults to n // n1), else :func:`default_factorization`.
    A split that does not multiply to n, or a factor outside
    [1, MAX_FACTOR], raises a ValueError."""
    if n1 is None:
        return default_factorization(n)
    fs = [f for f in (n1, n2, n3) if f is not None]
    if len(fs) == 1:
        fs.append(n // n1)
    fs = tuple(int(f) for f in fs)
    if math.prod(fs) != n:
        raise ValueError(f"factors {fs} do not multiply to n={n}")
    for f in fs:
        if f < 1 or f > MAX_FACTOR:
            raise ValueError(
                f"factor {f} is not between 1 and {MAX_FACTOR} (the MXU "
                f"edge): {fs}")
    return fs


def dispatch_scope(n: int, factors: Sequence[int]) -> str:
    """The name of one transform in a profile: its length and split
    (``dft6144_48x128``), or ``fft<n>`` where it runs no DFT matmuls
    (the Stockham baseline)."""
    if not factors:
        return f"fft{n}"
    return f"dft{n}_" + "x".join(str(f) for f in factors)


@dataclasses.dataclass(frozen=True)
class SpectralSpec:
    """Static configuration of one fused spectral dispatch."""

    n: int                      # FFT length (the transformed axis)
    fwd: bool                   # forward FFT first?
    filter_mode: str            # FILTER_*
    inv: bool                   # inverse FFT last?
    axis: int = 1               # 1 = rows pipeline (last axis), 0 = columns
    block: int = 8              # lines (rows kernel) / columns (cols kernel) per grid step
    batch_block: Optional[int] = None  # scenes per grid step (None = all)
    n1: Optional[int] = None    # mixed-radix factorization (defaults to
    n2: Optional[int] = None    # default_factorization's 2- or 3-way split)
    n3: Optional[int] = None
    fft_impl: str = "matmul"    # 'matmul' (MXU) | 'stockham' (VPU scalar baseline)
    karatsuba: bool = False     # 3-matmul complex product instead of 4
    precision: str = "f32"      # PRECISIONS key (matmul operands; f32 accum)
    fold_scale: bool = True     # fold the IFFT 1/N into the filter/final store
    outer_rank: int = 1         # K of the rank-K FILTER_OUTER phase

    def factors(self) -> tuple[int, ...]:
        """The mixed-radix decomposition n = n1 * n2 [* n3], every factor
        between 1 and 128 (see the module docstring for the rules)."""
        return resolve_factors(self.n, self.n1, self.n2, self.n3)

    @property
    def num_dft_consts(self) -> int:
        """Operand count for the DFT constants: one (re, im) matrix pair per
        factor plus one (re, im) twiddle pair per inter-stage boundary."""
        k = len(self.factors())
        return 4 * k - 2


# ---------------------------------------------------------------------------
# DFT constants (host-side numpy; passed to the kernel as broadcast operands)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def dft_constants(*factors: int) -> tuple[np.ndarray, ...]:
    """DFT matrices and inter-stage twiddles for a mixed-radix factor list.

    Returns, split re/im and in order: one (f_i, f_i) DFT matrix per factor,
    then one (f_i, prod(f_{i+1:})) twiddle per non-final stage, where the
    stage-i twiddle is exp(-2j pi k_i j / prod(f_{i:})) — the classic
    four-step twiddle, applied recursively. For two factors this is exactly
    (F1, F2, tw(n1, n2)); three factors add F3 and a (f2, f3) twiddle.

    Memoized per factorization (the key is the factor tuple itself):
    ``build_spectral_call`` and every jit re-trace would otherwise rebuild
    the same numpy matrices — an O(n·f) host cost per trace that is pure
    waste, since the constants depend on nothing but the factors. The
    cached arrays are marked read-only so no caller can mutate the shared
    copies (``dft_constants.cache_info()`` is asserted in tests).
    """
    def dft(n):
        k = np.arange(n)
        m = np.exp(-2j * np.pi * np.outer(k, k) / n)
        return m.real.astype(np.float32), m.imag.astype(np.float32)

    out: list[np.ndarray] = []
    for f in factors:
        out.extend(dft(f))
    for i in range(len(factors) - 1):
        rest = int(np.prod(factors[i + 1:]))
        k = np.arange(factors[i])[:, None]
        j = np.arange(rest)[None, :]
        tw = np.exp(-2j * np.pi * k * j / (factors[i] * rest))
        out.append(tw.real.astype(np.float32))
        out.append(tw.imag.astype(np.float32))
    for a in out:
        a.setflags(write=False)
    return tuple(out)


# ---------------------------------------------------------------------------
# In-kernel complex helpers (split re/im)
# ---------------------------------------------------------------------------

def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _cast(x, precision: str):
    prec = PRECISIONS[precision]
    return x if prec.dtype == "float32" else x.astype(prec.jnp_dtype)


def _cdot(fr, fi, xr, xi, dims, *, karatsuba: bool, precision: str):
    """Complex dot_general: (fr + i fi) . (xr + i xi) with contraction `dims`.

    4 real matmuls, or 3 with Karatsuba (P3 = (Fr+Fi)(Xr+Xi)). f32
    accumulate; f32 operands are multiplied at full precision (a TPU's
    default f32 matmul would round them to bf16 first).
    """
    dg = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(dims, ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if PRECISIONS[precision].dtype == "float32" else None),
    )
    fr_, fi_ = _cast(fr, precision), _cast(fi, precision)
    xr_, xi_ = _cast(xr, precision), _cast(xi, precision)
    if karatsuba:
        p1 = dg(fr_, xr_)
        p2 = dg(fi_, xi_)
        p3 = dg(_cast(fr + fi, precision), _cast(xr + xi, precision))
        return p1 - p2, p3 - p1 - p2
    yr = dg(fr_, xr_) - dg(fi_, xi_)
    yi = dg(fr_, xi_) + dg(fi_, xr_)
    return yr, yi


# ---------------------------------------------------------------------------
# Four-step matmul FFT, in-kernel: lines ride the lane dimension
# ---------------------------------------------------------------------------
#
# Every transform runs on an (N, C) slab whose C lines sit in the 128-wide
# lane dimension and whose transform axis N splits only along sublanes and
# leading dims. Mosaic cannot split a lane dimension into 64-wide pieces
# (the (L, 4096) -> (L, 64, 64) reshape of a row-major four-step), so a
# rows dispatch transposes its (L, N) block to (N, L) in VMEM, runs the
# same column recursion, and transposes back.

def _split_consts(consts, factors):
    """(per-stage DFT matrix pairs, per-boundary twiddle pairs)."""
    k = len(factors)
    mats = [(consts[2 * i], consts[2 * i + 1]) for i in range(k)]
    tws = [(consts[2 * k + 2 * i], consts[2 * k + 2 * i + 1])
           for i in range(k - 1)]
    return mats, tws


def _fft_cols_matmul(xr, xi, consts, spec: SpectralSpec):
    """Mixed-radix four-step FFT along axis 0 of an (N, C) column slab.

    Recursive Cooley-Tukey over spec.factors(): at stage i the (m, *batch)
    slab (m = prod of the remaining factors) splits its leading axis to
    (f_i, m/f_i, *batch), is contracted with the f_i-point DFT matrix on
    the MXU, twiddled, and the (m/f_i) remainder is transformed with the
    stage-i output index riding along as one more batch dim. The split,
    the swap of the two leading dims and the final merge never touch the
    lane dimension.
    """
    factors = spec.factors()
    mats, tws = _split_consts(consts, factors)
    kw = dict(karatsuba=spec.karatsuba, precision=spec.precision)

    def rec(xr, xi, i):
        # xr/xi: (m, *batch) — transform axis 0, m = prod(factors[i:])
        m, batch = xr.shape[0], xr.shape[1:]
        f = factors[i]
        fr, fi = mats[i]
        if i == len(factors) - 1:
            return _cdot(fr, fi, xr, xi, ((1,), (0,)), **kw)
        rest = m // f
        x3r = xr.reshape(f, rest, *batch)
        x3i = xi.reshape(f, rest, *batch)
        # stage A: contract f with F_i -> (f, rest, *batch)
        ar, ai = _cdot(fr, fi, x3r, x3i, ((1,), (0,)), **kw)
        twr, twi = tws[i]
        tshape = (f, rest) + (1,) * len(batch)
        br, bi = _cmul(ar, ai, twr.reshape(tshape), twi.reshape(tshape))
        # recurse along the remaining length: (rest, f, *batch)
        zr, zi = rec(jnp.swapaxes(br, 0, 1), jnp.swapaxes(bi, 0, 1), i + 1)
        # out[k_rest * f + k_i] = z[k_rest, k_i] — a leading-dim merge
        return zr.reshape(m, *batch), zi.reshape(m, *batch)

    return rec(xr, xi, 0)


# ---------------------------------------------------------------------------
# Stockham VPU FFT, in-kernel (the paper's 'scalar' baseline, radix-4 + radix-2)
# ---------------------------------------------------------------------------

def _fft_stockham(xr, xi, spec: SpectralSpec, axis: int):
    """Self-sorting Stockham along `axis` of a 2-D block, pure vector ops."""
    if axis == 0:  # operate on (N, C): move to (C, N), reuse rows code, move back
        yr, yi = _fft_stockham(xr.T, xi.T, spec, 1)
        return yr.T, yi.T
    L, N = xr.shape
    yr = xr.reshape(L, N, 1)
    yi = xi.reshape(L, N, 1)
    n, s = N, 1
    while n > 1:
        if n % 4 == 0:
            m = n // 4
            k = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0).astype(jnp.float32)
            th = (-2.0 * math.pi / n) * k
            w1r, w1i = jnp.cos(th), jnp.sin(th)
            w2r, w2i = _cmul(w1r, w1i, w1r, w1i)
            w3r, w3i = _cmul(w2r, w2i, w1r, w1i)
            sl = lambda z, q: z[:, q * m:(q + 1) * m, :]
            a_r, a_i = sl(yr, 0), sl(yi, 0)
            b_r, b_i = sl(yr, 1), sl(yi, 1)
            c_r, c_i = sl(yr, 2), sl(yi, 2)
            d_r, d_i = sl(yr, 3), sl(yi, 3)
            apc_r, apc_i = a_r + c_r, a_i + c_i
            amc_r, amc_i = a_r - c_r, a_i - c_i
            bpd_r, bpd_i = b_r + d_r, b_i + d_i
            bmd_r, bmd_i = b_r - d_r, b_i - d_i
            t0r, t0i = apc_r + bpd_r, apc_i + bpd_i
            # (amc - i*bmd) * w1
            u1r, u1i = amc_r + bmd_i, amc_i - bmd_r
            t1r, t1i = _cmul(u1r, u1i, w1r, w1i)
            # (apc - bpd) * w2
            t2r, t2i = _cmul(apc_r - bpd_r, apc_i - bpd_i, w2r, w2i)
            # (amc + i*bmd) * w3
            u3r, u3i = amc_r - bmd_i, amc_i + bmd_r
            t3r, t3i = _cmul(u3r, u3i, w3r, w3i)
            yr = jnp.stack([t0r, t1r, t2r, t3r], axis=2).reshape(L, m, 4 * s)
            yi = jnp.stack([t0i, t1i, t2i, t3i], axis=2).reshape(L, m, 4 * s)
            n, s = m, 4 * s
        else:
            m = n // 2
            k = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0).astype(jnp.float32)
            th = (-2.0 * math.pi / n) * k
            wr, wi = jnp.cos(th), jnp.sin(th)
            a_r, a_i = yr[:, :m, :], yi[:, :m, :]
            b_r, b_i = yr[:, m:, :], yi[:, m:, :]
            t1r, t1i = _cmul(a_r - b_r, a_i - b_i, wr, wi)
            yr = jnp.stack([a_r + b_r, t1r], axis=2).reshape(L, m, 2 * s)
            yi = jnp.stack([a_i + b_i, t1i], axis=2).reshape(L, m, 2 * s)
            n, s = m, 2 * s
    return yr.reshape(L, N), yi.reshape(L, N)


# ---------------------------------------------------------------------------
# The fused kernel body: [FFT] -> [multiply] -> [IFFT], one dispatch
# ---------------------------------------------------------------------------

def _run_fft(xr, xi, consts, spec: SpectralSpec, inverse: bool):
    """Forward or inverse (conj-FFT-conj) transform along spec.axis.

    x is a (Bb, L, n) / (Bb, n, L) batch block. Each scene of the block
    runs through the same per-scene code — one grid step loads the DFT
    constants once for all of them, and a scene's lines see identical
    matmul shapes whatever the batch block, so batched images equal
    unbatched ones. Rows blocks transpose to the (n, L) column layout and
    back (see ``_fft_cols_matmul``).
    """
    if spec.fft_impl not in ("matmul", "stockham"):
        raise ValueError(f"unknown fft_impl {spec.fft_impl}")
    outs_r, outs_i = [], []
    for b in range(xr.shape[0]):
        yr, yi = xr[b], xi[b]
        if inverse:
            yi = -yi
        if spec.fft_impl == "stockham":
            yr, yi = _fft_stockham(yr, yi, spec, spec.axis)
        elif spec.axis == 1:
            yr, yi = _fft_cols_matmul(yr.T, yi.T, consts, spec)
            yr, yi = yr.T, yi.T
        else:
            yr, yi = _fft_cols_matmul(yr, yi, consts, spec)
        if inverse:
            # conj + 1/N, folded into the final store (paper SSII-C)
            scale = 1.0 / spec.n
            yr, yi = yr * scale, yi * (-scale)
        outs_r.append(yr)
        outs_i.append(yi)
    return jnp.stack(outs_r), jnp.stack(outs_i)


def _filter_ref_count(filter_mode: str) -> int:
    """Operand count of one kernel filter payload, by mode."""
    return {FILTER_NONE: 0, FILTER_SHARED: 2, FILTER_FULL: 2,
            FILTER_OUTER: 2, FILTER_SHARED_OUTER: 4}[filter_mode]


def _apply_filters(xr, xi, axis: int, filter_mode: str, filt):
    """Apply one composed kernel filter to an (..., L, n) / (..., n, L)
    block. ``filt`` holds the mode's refs or arrays (hr/hi, u/v, or both);
    2-D payloads broadcast right-aligned over any leading batch dim."""

    def _apply_outer(xr, xi, u_ref, v_ref):
        u = u_ref[...]      # rows: (L, K); cols: (K, C)  — per-line parameters
        v = v_ref[...]      # rows: (K, N); cols: (N, K)  — per-sample parameters
        # rank-K phase synthesized in VMEM (no 2-D filter I/O); the 2-D
        # phase broadcasts across the leading batch-block dim. K is 1-2:
        # a sum of K outer products on the VPU keeps the phase exact f32,
        # where a TPU matmul's default pass rounds u and v to bf16 (the
        # azimuth phase of a 4096² scene reaches ~480 rad, so bf16
        # operands put radians of error into it)
        a, b = (u, v) if axis == 1 else (v, u)
        phase = a[:, 0:1] * b[0:1, :]
        for k in range(1, a.shape[1]):
            phase = phase + a[:, k:k + 1] * b[k:k + 1, :]
        return _cmul(xr, xi, jnp.cos(phase), jnp.sin(phase))

    if filter_mode in (FILTER_SHARED, FILTER_FULL):
        # FILTER_SHARED blocks are (1, N) [rows] or (N, 1) [cols]: broadcast.
        xr, xi = _cmul(xr, xi, filt[0][...], filt[1][...])
    elif filter_mode == FILTER_OUTER:
        xr, xi = _apply_outer(xr, xi, filt[0], filt[1])
    elif filter_mode == FILTER_SHARED_OUTER:
        xr, xi = _cmul(xr, xi, filt[0][...], filt[1][...])
        xr, xi = _apply_outer(xr, xi, filt[2], filt[3])
    return xr, xi


def line_exponents(xr, xi, axis: int):
    """bs16 codec, extract half: one power-of-two exponent per line along
    the free axis of `axis`-oriented data, reduced over the transform axis
    (the last dim when axis=1, the second-to-last when axis=0; any leading
    dims are batch). Each segment is linear per line, so scales factored
    out per line and re-applied in the epilogue are exact up to f32
    rounding — and power-of-two scaling is itself bit-exact.

    Per-line granularity is the route-invisibility property: the exponent
    of a line depends only on that line's values, never on how the grid
    blocked lines/batches/phases or how devices sharded the free axis, so
    every route quantizes identically (asserted across fused3 / fused1
    vmem+staged / 8-device sharded in tests/test_quality_regression.py).
    The 1e-37 floor keeps all-zero (e.g. padded) lines at a finite
    exponent; zero stays exactly zero through scale and unscale. The
    clamp to [-126, 126] keeps `_pow2` exact for BOTH exp and -exp
    (an amax past 2^126 would have overflowed the FFT long before)."""
    red = xr.ndim - 1 if axis == 1 else xr.ndim - 2
    amax = jnp.maximum(jnp.max(jnp.abs(xr), axis=red, keepdims=True),
                       jnp.max(jnp.abs(xi), axis=red, keepdims=True))
    exp = jnp.ceil(jnp.log2(jnp.maximum(amax, jnp.float32(1e-37))))
    return jnp.clip(exp, jnp.float32(-126.0), jnp.float32(126.0))


def _pow2(exp):
    """Exactly 2^exp for integer-valued f32 exp in [-126, 126], built by
    placing exp straight into the f32 exponent bits. `jnp.exp2` is NOT
    exact on every backend (CPU lowers it through exp(x·ln2), so e.g.
    exp2(17) != 131072), and an inexact scale would break the codec's
    round-trip identity (tests/test_kernels.py::test_bs16_codec_round_trip)."""
    bits = (exp.astype(jnp.int32) + 127) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def apply_exponents(xr, xi, exp):
    """bs16 codec, apply half: fold per-line exponents back in (exact)."""
    scale = _pow2(exp)
    return xr * scale, xi * scale


def remove_exponents(xr, xi, exp):
    """Scale per-line exponents out (exact): x -> x * 2^-exp."""
    inv = _pow2(-exp)
    return xr * inv, xi * inv


def _spectral_kernel(spec: SpectralSpec, *refs):
    """Pallas kernel body. Ref layout (in order):

    xr, xi, [DFT matrices + twiddles if matmul], [filter refs...], or, oi

    The x/output refs are (Bb, L, n) rows / (Bb, n, L) cols batch blocks:
    each grid step holds the SAME line-block of every scene in the batch
    block, so the DFT constants and filters are shared across scenes (the
    2-D filters broadcast right-aligned over the leading batch dim).
    """
    it = iter(refs)
    xr_ref, xi_ref = next(it), next(it)
    consts = None
    if spec.fft_impl == "matmul" and (spec.fwd or spec.inv):
        consts = tuple(next(it)[...] for _ in range(spec.num_dft_consts))
    filt = tuple(next(it) for _ in range(_filter_ref_count(spec.filter_mode)))
    or_ref, oi_ref = next(it), next(it)

    xr = xr_ref[...]
    xi = xi_ref[...]

    exp = None
    if PRECISIONS[spec.precision].block_scaled:
        exp = line_exponents(xr, xi, spec.axis)
        xr, xi = remove_exponents(xr, xi, exp)

    if spec.fwd:
        xr, xi = _run_fft(xr, xi, consts, spec, inverse=False)

    xr, xi = _apply_filters(xr, xi, spec.axis, spec.filter_mode, filt)

    if spec.inv:
        xr, xi = _run_fft(xr, xi, consts, spec, inverse=True)

    if exp is not None:
        # bs16 epilogue: fold the per-line exponents back into the store
        xr, xi = apply_exponents(xr, xi, exp)

    or_ref[...] = xr.reshape(or_ref.shape)
    oi_ref[...] = xi.reshape(oi_ref.shape)


# ---------------------------------------------------------------------------
# pallas_call builder
# ---------------------------------------------------------------------------

def _flops_per_line(spec: SpectralSpec) -> float:
    """Nominal 5 N log2 N per transform + 6N per complex multiply (for benches)."""
    n = spec.n
    f = 0.0
    if spec.fwd:
        f += 5.0 * n * math.log2(n)
    if spec.inv:
        f += 5.0 * n * math.log2(n)
    if spec.filter_mode != FILTER_NONE:
        f += 6.0 * n
    return f


def tile_bytes(shape, itemsize: int = 4) -> int:
    """VMEM bytes of one array laid out in (8, 128) tiles: the last dim
    pads to a multiple of 128 lanes, the one before it to 8 sublanes."""
    shape = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    *lead, r, c = shape
    return (math.prod(lead) * (-(-r // 8) * 8) * (-(-c // 128) * 128)
            * itemsize)


# Live (n, L) f32 temporaries one scene's transform keeps in VMEM at its
# peak (re/im of the loaded block, the transposed copy, the four real
# products of a complex matmul, the twiddled and swapped stage outputs),
# fitted so the estimate bounds what Mosaic allocates for the v5e
# compiles in tests/test_tpu_compile.py.
_TEMP_SLABS = 16


def _filter_block_shapes(filter_mode: str, axis: int, n: int, L: int,
                         K: int) -> list:
    if axis == 1:
        shared, full, u, v = (1, n), (L, n), (L, K), (K, n)
    else:
        shared, full, u, v = (n, 1), (n, L), (K, L), (n, K)
    return {FILTER_NONE: [], FILTER_SHARED: [shared, shared],
            FILTER_FULL: [full, full], FILTER_OUTER: [u, v],
            FILTER_SHARED_OUTER: [shared, shared, u, v]}[filter_mode]


def _const_shapes(spec: SpectralSpec) -> list:
    if spec.fft_impl != "matmul" or not (spec.fwd or spec.inv):
        return []
    return [c.shape for c in dft_constants(*spec.factors())]


def spectral_vmem_bytes(spec: SpectralSpec, batch_block: int = 1) -> int:
    """Estimated VMEM of one grid step of a per-axis dispatch: the x and
    y blocks double-buffered by the Pallas pipeline, the constant and
    filter blocks (also double-buffered), and the transform's in-kernel
    temporaries. ``compiler_params`` sizes the Mosaic limit from it and
    the tuner's feasibility cut (``repro.tuning.cost``) reads it."""
    n, L = spec.n, spec.block
    blk = tile_bytes((batch_block, L, n) if spec.axis == 1
                     else (batch_block, n, L))
    scene = tile_bytes((n, L))
    total = 8 * blk + 4 * scene * batch_block + _TEMP_SLABS * scene
    total += 2 * sum(tile_bytes(s) for s in _const_shapes(spec))
    total += 2 * sum(tile_bytes(s) for s in _filter_block_shapes(
        spec.filter_mode, spec.axis, n, L, spec.outer_rank))
    return total


def default_batch_block(spec: SpectralSpec, batch: int) -> int:
    """Scenes per grid step when none is pinned: the whole batch if its
    block fits the device's per-step VMEM budget, else the largest
    divisor of ``batch`` that does (at least 1)."""
    budget = _device_spec().vmem_budget_bytes
    for bb in range(batch, 0, -1):
        if batch % bb == 0 and (
                bb == 1 or spectral_vmem_bytes(spec, bb) <= budget):
            return bb
    return 1


def build_spectral_call(spec: SpectralSpec, lines: int, batch: int = 1,
                        interpret: bool = False, config=None):
    """Returns fn(xr, xi, *filter_args) -> (yr, yi) as a single pallas_call.

    ``config`` is an optional :class:`repro.tuning.KernelConfig`: its
    non-None knobs (block, n1/n2/n3, karatsuba, precision) are applied on
    top of ``spec`` before the call is built — the one config path from
    the tuning subsystem into the kernel layer. (Duck-typed through
    ``config.apply(spec)``; kernels do not import repro.tuning.)

    Rows pipeline: x is (B, lines, N), cols pipeline: x is (B, N, lines).
    The grid runs over (batch-blocks, line-blocks) with each grid step
    holding a (Bb, L, N) slab — the same line-block of Bb scenes at once —
    so the DFT-constant loads and the per-step dispatch overhead amortize
    across the batch (spec.batch_block defaults to the largest divisor of
    the batch whose block fits the device's VMEM budget,
    :func:`default_batch_block`). Filters are 2-D and batch-shared
    (every scene uses the same SceneConfig filters).
    """
    if config is not None:
        spec = config.apply(spec)
    check_precision(spec.precision, interpret)
    check_length(spec)
    n = spec.n
    L = spec.block
    if lines % L:
        raise ValueError(f"lines={lines} not divisible by block={L}")
    Bb = spec.batch_block or default_batch_block(spec, batch)
    if batch % Bb:
        raise ValueError(f"batch={batch} not divisible by batch_block={Bb}")
    grid = (batch // Bb, lines // L)

    K = spec.outer_rank
    if spec.axis == 1:
        x_shape = (batch, lines, n)
        x_spec = pl.BlockSpec((Bb, L, n), lambda b, i: (b, i, 0))
        shared_spec = pl.BlockSpec((1, n), lambda b, i: (0, 0))
        full_spec = pl.BlockSpec((L, n), lambda b, i: (i, 0))
        u_spec = pl.BlockSpec((L, K), lambda b, i: (i, 0))   # (lines, K)
        v_spec = pl.BlockSpec((K, n), lambda b, i: (0, 0))   # (K, n)
    else:
        x_shape = (batch, n, lines)
        x_spec = pl.BlockSpec((Bb, n, L), lambda b, i: (b, 0, i))
        shared_spec = pl.BlockSpec((n, 1), lambda b, i: (0, 0))
        full_spec = pl.BlockSpec((n, L), lambda b, i: (0, i))
        u_spec = pl.BlockSpec((K, L), lambda b, i: (0, i))   # (K, lines)
        v_spec = pl.BlockSpec((n, K), lambda b, i: (0, 0))   # (n, K)

    in_specs = [x_spec, x_spec]
    extra_args: list[jnp.ndarray] = []

    needs_consts = spec.fft_impl == "matmul" and (spec.fwd or spec.inv)
    if needs_consts:
        consts = dft_constants(*spec.factors())
        in_specs += [pl.BlockSpec(c.shape, lambda b, i: (0, 0))
                     for c in consts]
        extra_args += [jnp.asarray(c) for c in consts]

    if spec.filter_mode == FILTER_SHARED:
        in_specs += [shared_spec, shared_spec]
    elif spec.filter_mode == FILTER_FULL:
        in_specs += [full_spec, full_spec]
    elif spec.filter_mode == FILTER_OUTER:
        in_specs += [u_spec, v_spec]
    elif spec.filter_mode == FILTER_SHARED_OUTER:
        in_specs += [shared_spec, shared_spec, u_spec, v_spec]

    out_specs = [x_spec, x_spec]
    out_shape = [
        jax.ShapeDtypeStruct(x_shape, jnp.float32),
        jax.ShapeDtypeStruct(x_shape, jnp.float32),
    ]

    kernel = functools.partial(_spectral_kernel, spec)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=compiler_params(spectral_vmem_bytes(spec, Bb),
                                        interpret),
        interpret=interpret,
        name=f"spectral_axis{spec.axis}",
    )

    def fn(xr, xi, *filter_args):
        args = [xr, xi] + extra_args + list(filter_args)
        return call(*args)

    fn.flops = _flops_per_line(spec) * lines * batch  # nominal, for benches
    return fn


# ---------------------------------------------------------------------------
# The single-dispatch 2-D megakernel: fft? mul* ifft? (turn fft? mul* ifft?)*
# ---------------------------------------------------------------------------
#
# The paper's headline is ONE dispatch for the whole imaging chain with every
# intermediate on-chip. The per-axis kernel above still forces one dispatch
# per transform axis because the range->azimuth corner turn is a fusion
# barrier. The megakernel removes it: a single pallas_call runs an arbitrary
# sequence of per-axis spectral *segments* (each `fft? mul* ifft?`, composed
# filters included) with the corner turns INSIDE the kernel, in one of two
# residency modes:
#
# RESIDENT_VMEM   The whole (Bb, na, nr) slab lives in VMEM for the entire
#                 grid step; a "turn" is purely logical (the cols transform
#                 contracts axis 0 of the same slab — no data movement).
#                 Zero HBM intermediates: the paper's claim realized on TPU,
#                 for scenes whose slab fits the device's VMEM budget
#                 (repro.tuning.cost.DEVICES). The TPU
#                 analogue of the Radix-8 Stockham two-tier register/
#                 threadgroup decomposition (arXiv 2603.27569) — VMEM plays
#                 the register tier.
# RESIDENT_STAGED Large scenes: one dispatch whose grid is split into one
#                 phase per segment. Each phase strips its free axis in
#                 `phase_block`-line blocks, manually DMA-staged between an
#                 HBM scratch buffer (the corner-turned intermediate) and
#                 double-buffered VMEM slabs, so the corner-turn DMA of
#                 block j+1 overlaps the DFT matmuls of block j. Bergach et
#                 al. (arXiv 1505.08067) show the global transpose, not the
#                 butterflies, dominates radar FFT pipelines — this schedule
#                 hides it behind compute instead of spending a dispatch +
#                 full HBM round-trip per axis change.
#
# Numerics: both modes run the exact same per-segment math as the per-axis
# kernel (same _run_fft, same filter application, same constants), and every
# segment treats its line blocks independently — so f32 results are
# bit-identical between the two modes AND to the equivalent multi-dispatch
# pipeline (asserted in tests/test_fused1.py). bs16 carries PER-LINE block
# exponents through the in-kernel corner turns: each segment boundary
# re-blocks (apply the carried exponents — exact power-of-two scaling —
# then re-extract along the new segment's free axis), which reproduces the
# multi-dispatch pipeline's per-dispatch extraction bit for bit. Because a
# line's exponent never depends on the grid blocking, bs16 is bit-identical
# across both residency modes, the per-axis chain, and the sharded lowering
# (tests/test_quality_regression.py, tests/test_service.py).


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    """One per-axis `fft? mul* ifft?` run inside a megakernel dispatch.

    The per-segment scheduling fields (``n1/n2/n3``, ``karatsuba``) let a
    tuned Schedule give EACH segment its own factorization and complex-
    product algorithm — the part of the schedule space a single global
    MegaSpec knob cannot express. ``None`` defers to the MegaSpec-level
    value (and from there to the library default), so legacy specs are
    unchanged."""

    axis: int                      # scene axis: 1 = range/rows, 0 = azimuth/cols
    fwd: bool = False
    inv: bool = False
    filter_mode: str = FILTER_NONE
    outer_rank: int = 1
    n1: Optional[int] = None       # per-segment factorization override
    n2: Optional[int] = None
    n3: Optional[int] = None
    karatsuba: Optional[bool] = None   # tri-state: None defers to MegaSpec


@dataclasses.dataclass(frozen=True)
class MegaSpec:
    """Static configuration of one single-dispatch 2-D megakernel."""

    na: int                        # azimuth lines (axis-0 FFT length)
    nr: int                        # range samples (axis-1 FFT length)
    segments: tuple[SegmentSpec, ...]
    residency: str = RESIDENT_VMEM
    batch_block: Optional[int] = None  # scenes per grid step, vmem mode
                                       # (None = 1: one scene slab per step
                                       # keeps the VMEM cut batch-invariant;
                                       # constants stay resident across
                                       # steps — their block never moves)
    phase_block: int = 8           # lines per staged-phase grid step
    buffer_depth: int = 2          # staged DMA slots (1 = no overlap)
    n1: Optional[int] = None       # range-axis factorization override
    n2: Optional[int] = None       #   (azimuth uses default_factorization;
    n3: Optional[int] = None       #    same convention as compile_plan's fft_kw)
    fft_impl: str = "matmul"
    karatsuba: bool = False
    precision: str = "f32"

    def __post_init__(self):
        if not self.segments:
            raise ValueError("MegaSpec needs at least one segment")
        if self.residency not in (RESIDENT_VMEM, RESIDENT_STAGED):
            raise ValueError(f"unknown residency {self.residency!r}")
        if self.buffer_depth < 1:
            raise ValueError(
                f"buffer_depth must be >= 1, got {self.buffer_depth}")
        for s in self.segments:
            if s.axis not in (0, 1):
                raise ValueError(f"segment axis must be 0 or 1, got {s.axis}")
            if not (s.fwd or s.inv or s.filter_mode != FILTER_NONE):
                raise ValueError("empty megakernel segment")
        resolve_precision(self.precision)

    def seg_spec(self, seg: SegmentSpec) -> SpectralSpec:
        """The per-axis SpectralSpec view of one segment (drives _run_fft
        and the DFT-constant layout — numerics identical to the per-axis
        kernel by construction). Factorization precedence: the segment's
        own override > the MegaSpec range-axis knobs (axis 1 only, the
        compile_plan fft_kw convention) > library default; karatsuba:
        segment override > MegaSpec global."""
        kw = {}
        if seg.axis == 1:
            kw = dict(n1=self.n1, n2=self.n2, n3=self.n3)
        if seg.n1 is not None:
            kw = dict(n1=seg.n1, n2=seg.n2, n3=seg.n3)
        kara = self.karatsuba if seg.karatsuba is None else seg.karatsuba
        return SpectralSpec(
            n=self.nr if seg.axis == 1 else self.na,
            fwd=seg.fwd, inv=seg.inv, filter_mode=seg.filter_mode,
            axis=seg.axis, fft_impl=self.fft_impl, karatsuba=kara,
            precision=self.precision, outer_rank=seg.outer_rank, **kw)

    @property
    def turns(self) -> int:
        """In-kernel corner turns (axis changes between segments)."""
        return sum(1 for a, b in zip(self.segments, self.segments[1:])
                   if a.axis != b.axis)


def _seg_const_key(spec: MegaSpec, seg: SegmentSpec) -> tuple:
    """The constants-sharing key of one segment: (axis, factorization).
    Segments on one axis share one broadcast-operand set ONLY while they
    agree on the factorization — a schedule that gives two same-axis
    segments different radix splits gets one set each."""
    return (seg.axis, spec.seg_spec(seg).factors())


def _mega_const_plan(spec: MegaSpec) -> list[tuple[tuple, tuple]]:
    """((axis, factors), dft_constants) per distinct transformed
    (axis, factorization), in first-use order — one set of broadcast
    operands shared by every segment (and every scene in the batch block)
    that transforms that axis with those factors."""
    out: list[tuple[tuple, tuple]] = []
    if spec.fft_impl != "matmul":
        return out
    seen = set()
    for seg in spec.segments:
        key = _seg_const_key(spec, seg)
        if (seg.fwd or seg.inv) and key not in seen:
            seen.add(key)
            out.append((key, dft_constants(*key[1])))
    return out


def _seg_filter_shapes(spec: MegaSpec, seg: SegmentSpec) -> list[tuple]:
    """Kernel-layout shapes of one segment's filter operands (whole-scene
    blocks; the megakernel never line-blocks its filters)."""
    na, nr, K = spec.na, spec.nr, seg.outer_rank
    if seg.axis == 1:
        shared, full = (1, nr), (na, nr)
        u, v = (na, K), (K, nr)
    else:
        shared, full = (na, 1), (na, nr)
        u, v = (K, nr), (na, K)
    return {
        FILTER_NONE: [],
        FILTER_SHARED: [shared, shared],
        FILTER_FULL: [full, full],
        FILTER_OUTER: [u, v],
        FILTER_SHARED_OUTER: [shared, shared, u, v],
    }[seg.filter_mode]


def _run_segment(xr, xi, consts, sspec: SpectralSpec, seg: SegmentSpec, filt):
    """One segment on a (Bb, na, nr) slab — the (Bb, L, n) rows layout and
    the (Bb, n, L) cols layout are BOTH the scene layout, so the corner
    turn between segments is purely logical."""
    if seg.fwd:
        xr, xi = _run_fft(xr, xi, consts, sspec, inverse=False)
    xr, xi = _apply_filters(xr, xi, seg.axis, seg.filter_mode, filt)
    if seg.inv:
        xr, xi = _run_fft(xr, xi, consts, sspec, inverse=True)
    return xr, xi


def _mega_kernel_resident(spec: MegaSpec, *refs):
    """VMEM-resident megakernel body. Ref order: xr, xi, [per-axis DFT
    constants], [per-segment filter refs], or, oi. The grid step holds a
    whole (Bb, na, nr) slab; every intermediate stays in VMEM."""
    it = iter(refs)
    xr_ref, xi_ref = next(it), next(it)
    const_plan = _mega_const_plan(spec)
    consts = {key: tuple(next(it)[...] for _ in range(len(cs)))
              for key, cs in const_plan}
    seg_filts = [tuple(next(it)
                       for _ in range(_filter_ref_count(s.filter_mode)))
                 for s in spec.segments]
    or_ref, oi_ref = next(it), next(it)

    xr = xr_ref[...]
    xi = xi_ref[...]
    block_scaled = PRECISIONS[spec.precision].block_scaled
    exp = None
    for i, (seg, filt) in enumerate(zip(spec.segments, seg_filts)):
        if block_scaled:
            if i == 0:
                # prologue: extract per-line exponents once per grid step
                exp = line_exponents(xr, xi, seg.axis)
            else:
                # corner turn (or same-axis boundary): re-block the carried
                # exponents alongside the data — apply exactly, re-extract
                # along the new free axis. Re-blocking at EVERY boundary
                # (not just axis changes) mirrors the multi-dispatch
                # pipeline's per-dispatch extraction, keeping the fused
                # route bit-identical to it.
                xr, xi = apply_exponents(xr, xi, exp)
                exp = line_exponents(xr, xi, seg.axis)
            xr, xi = remove_exponents(xr, xi, exp)
        xr, xi = _run_segment(xr, xi, consts.get(_seg_const_key(spec, seg)),
                              spec.seg_spec(seg), seg, filt)
    if exp is not None:
        # epilogue: the carried exponents land once, at the final store
        xr, xi = apply_exponents(xr, xi, exp)
    or_ref[...] = xr.reshape(or_ref.shape)
    oi_ref[...] = xi.reshape(oi_ref.shape)


def _staged_phases(spec: MegaSpec) -> tuple[list[dict], int]:
    """Static phase schedule of the scratch-staged megakernel: one phase
    per segment, stripping its free axis in `phase_block`-line blocks.
    Returns (phases, total grid steps). Phase p reads from the raw input
    (p=0) or the HBM scratch, and writes to the output (last p) or back
    to the scratch — in-place when the axis repeats, corner-turned when
    it flips (col-blocks written, row-blocks read, or vice versa)."""
    phases: list[dict] = []
    off = 0
    last = len(spec.segments) - 1
    for i, seg in enumerate(spec.segments):
        lines = spec.na if seg.axis == 1 else spec.nr
        pb = min(spec.phase_block, lines)
        if lines % pb:
            raise ValueError(
                f"phase_block={pb} does not divide the free axis "
                f"({lines} lines) of segment {i}")
        phases.append(dict(
            seg=seg, idx=i, axis=seg.axis, pb=pb, nblocks=lines // pb,
            offset=off, src="x" if i == 0 else "scratch",
            dst="out" if i == last else "scratch"))
        off += lines // pb
    return phases, off


# DMA semaphore channels of the staged kernel, per double-buffer slot.
_SEM_IN_R, _SEM_IN_I, _SEM_F_R, _SEM_F_I, _SEM_OUT_R, _SEM_OUT_I = range(6)


def _mega_kernel_staged(spec: MegaSpec, *refs):
    """Scratch-staged megakernel body — grid (B, total_steps).

    Ref order: xr, xi (ANY), [per-axis DFT constants (VMEM)],
    [per-segment filters: FULL pairs in ANY (DMA-sliced with the line
    block), everything else resident in VMEM], the outputs or, oi and
    sr, si (ANY — sr/si is the HBM corner-turn intermediate, an output
    only because Mosaic allocates scratch in VMEM/SMEM alone; the wrapper
    drops it), then scratch: the double-buffered VMEM line slabs (rows and/or cols orientation, plus
    FULL-filter slabs where needed), the bs16 per-line exponent-state
    vectors er (na, 1) / ec (1, nr) when the precision is block-scaled,
    and the DMA semaphores (2 slots x 6
    channels). Each step waits for its own slot's input DMA, immediately
    starts the NEXT block's input DMA into the other slot, then runs the
    segment's DFT matmuls — the copy/compute overlap the dispatch count
    alone cannot buy.
    """
    phases, _ = _staged_phases(spec)
    it = iter(refs)
    xr_ref, xi_ref = next(it), next(it)
    const_plan = _mega_const_plan(spec)
    consts = {key: tuple(next(it)[...] for _ in range(len(cs)))
              for key, cs in const_plan}
    seg_filts = [tuple(next(it)
                       for _ in range(_filter_ref_count(s.filter_mode)))
                 for s in spec.segments]
    or_ref, oi_ref = next(it), next(it)
    sr_ref, si_ref = next(it), next(it)
    bufs = {}
    if any(p["axis"] == 1 for p in phases):
        bufs[1] = next(it)
    if any(p["axis"] == 0 for p in phases):
        bufs[0] = next(it)
    fbufs = {}
    if any(p["axis"] == 1 and p["seg"].filter_mode == FILTER_FULL
           for p in phases):
        fbufs[1] = next(it)
    if any(p["axis"] == 0 and p["seg"].filter_mode == FILTER_FULL
           for p in phases):
        fbufs[0] = next(it)
    block_scaled = PRECISIONS[spec.precision].block_scaled
    er_ref = ec_ref = None
    if block_scaled:
        # carried per-line exponent state (bs16): the row-axis and
        # col-axis exponent vectors persist in VMEM across the sequential
        # phase steps (the same cross-step scratch persistence the
        # double-buffer prefetch relies on), so the HBM scratch holds
        # SCALED data end to end and the exponents ride the corner turn
        # in these vectors instead of being re-derived from scratch reads.
        er_ref = next(it)              # (na, 1): axis-1 (row) exponents
        ec_ref = next(it)              # (1, nr): axis-0 (col) exponents
    sems = next(it)

    b = pl.program_id(0)
    s = pl.program_id(1)

    def _sliced(ref, axis: int, lo, pb: int, batched: bool):
        """A (pb, nr) row / (na, pb) col slab slice of a scene ref."""
        if axis == 1:
            return ref.at[b, pl.ds(lo, pb), :] if batched \
                else ref.at[pl.ds(lo, pb), :]
        return ref.at[b, :, pl.ds(lo, pb)] if batched \
            else ref.at[:, pl.ds(lo, pb)]

    for p in phases:
        seg, axis, pb = p["seg"], p["axis"], p["pb"]
        off, nb = p["offset"], p["nblocks"]
        prev_axis = phases[p["idx"] - 1]["axis"] if p["idx"] else None
        buf = bufs[axis]
        fbuf = fbufs.get(axis)
        sspec = spec.seg_spec(seg)
        filt_refs = seg_filts[p["idx"]]
        has_full = seg.filter_mode == FILTER_FULL
        src_r, src_i = ((xr_ref, xi_ref) if p["src"] == "x"
                        else (sr_ref, si_ref))
        dst_r, dst_i = ((or_ref, oi_ref) if p["dst"] == "out"
                        else (sr_ref, si_ref))
        src_batched = p["src"] == "x"
        dst_batched = p["dst"] == "out"

        def in_copies(j, slot, seg=seg, axis=axis, pb=pb, buf=buf, fbuf=fbuf,
                      src_r=src_r, src_i=src_i, src_batched=src_batched,
                      filt_refs=filt_refs, has_full=has_full):
            lo = j * pb
            cps = [
                pltpu.make_async_copy(
                    _sliced(src_r, axis, lo, pb, src_batched),
                    buf.at[slot, 0], sems.at[slot, _SEM_IN_R]),
                pltpu.make_async_copy(
                    _sliced(src_i, axis, lo, pb, src_batched),
                    buf.at[slot, 1], sems.at[slot, _SEM_IN_I]),
            ]
            if has_full:
                cps += [
                    pltpu.make_async_copy(
                        _sliced(filt_refs[0], axis, lo, pb, False),
                        fbuf.at[slot, 0], sems.at[slot, _SEM_F_R]),
                    pltpu.make_async_copy(
                        _sliced(filt_refs[1], axis, lo, pb, False),
                        fbuf.at[slot, 1], sems.at[slot, _SEM_F_I]),
                ]
            return cps

        @pl.when((s >= off) & (s < off + nb))
        def _(p=p, seg=seg, axis=axis, pb=pb, off=off, nb=nb, buf=buf,
              fbuf=fbuf, sspec=sspec, filt_refs=filt_refs,
              has_full=has_full, dst_r=dst_r, dst_i=dst_i,
              dst_batched=dst_batched, in_copies=in_copies,
              prev_axis=prev_axis):
            j = s - off
            depth = spec.buffer_depth
            if depth == 1:
                # single slot: no copy/compute overlap — fetch, wait, run
                slot = 0
                for cp in in_copies(j, 0):
                    cp.start()
                for cp in in_copies(j, 0):
                    cp.wait()
            else:
                slot = jax.lax.rem(j, depth)

                @pl.when(j == 0)
                def _():                   # phase start: blocking first fetch
                    for cp in in_copies(0, 0):
                        cp.start()
                for cp in in_copies(j, slot):
                    cp.wait()
                @pl.when(j + 1 < nb)
                def _():                   # prefetch overlaps the matmuls
                    for cp in in_copies(j + 1, jax.lax.rem(j + 1, depth)):
                        cp.start()

            xr = buf[slot, 0][None]
            xi = buf[slot, 1][None]
            lo = j * pb
            exp = None
            if block_scaled:
                if p["src"] != "x":
                    # the scratch slab is scaled: unscale this block with
                    # the exponent state the previous phase wrote — its
                    # own lines' slice when the axis repeats, the whole
                    # other-axis vector across a corner turn (every
                    # element of a turned block crosses every prior line)
                    if prev_axis == 1:
                        old = (er_ref[pl.ds(lo, pb), :] if axis == 1
                               else er_ref[...])
                    else:
                        old = (ec_ref[:, pl.ds(lo, pb)] if axis == 0
                               else ec_ref[...])
                    xr, xi = apply_exponents(xr, xi, old[None])
                # re-block: per-line exponents along THIS phase's free
                # axis — identical to the per-dispatch extraction of the
                # multi-dispatch pipeline, hence route-invisible
                exp = line_exponents(xr, xi, axis)
                xr, xi = remove_exponents(xr, xi, exp)
                if axis == 1:
                    er_ref[pl.ds(lo, pb), :] = exp[0]
                else:
                    ec_ref[:, pl.ds(lo, pb)] = exp[0]
            if seg.filter_mode == FILTER_NONE:
                filt = ()
            elif has_full:
                filt = (fbuf[slot, 0], fbuf[slot, 1])
            elif seg.filter_mode == FILTER_SHARED:
                filt = (filt_refs[0][...], filt_refs[1][...])
            else:
                # OUTER / SHARED_OUTER: the per-line u factor is sliced to
                # the block in VMEM; shared vectors and v ride whole.
                if axis == 1:
                    u = filt_refs[-2][pl.ds(lo, pb), :]
                    v = filt_refs[-1][...]
                else:
                    u = filt_refs[-2][:, pl.ds(lo, pb)]
                    v = filt_refs[-1][...]
                if seg.filter_mode == FILTER_SHARED_OUTER:
                    filt = (filt_refs[0][...], filt_refs[1][...], u, v)
                else:
                    filt = (u, v)
            xr, xi = _run_segment(xr, xi, consts.get(_seg_const_key(spec, seg)),
                                  sspec, seg, filt)
            if exp is not None and p["dst"] == "out":
                # epilogue: the exponents land once, at the final store;
                # scratch-bound intermediates stay scaled (the carried
                # state rides er/ec through the corner turn instead)
                xr, xi = apply_exponents(xr, xi, exp)
            buf[slot, 0] = xr[0]
            buf[slot, 1] = xi[0]
            out_r = pltpu.make_async_copy(
                buf.at[slot, 0], _sliced(dst_r, axis, lo, pb, dst_batched),
                sems.at[slot, _SEM_OUT_R])
            out_i = pltpu.make_async_copy(
                buf.at[slot, 1], _sliced(dst_i, axis, lo, pb, dst_batched),
                sems.at[slot, _SEM_OUT_I])
            out_r.start()
            out_i.start()
            out_r.wait()
            out_i.wait()


def _mega_flops(spec: MegaSpec) -> float:
    """Nominal algorithmic FLOPs of one scene through every segment."""
    total = 0.0
    for seg in spec.segments:
        lines = spec.na if seg.axis == 1 else spec.nr
        total += _flops_per_line(spec.seg_spec(seg)) * lines
    return total


def build_mega_call(spec: MegaSpec, batch: int = 1,
                    interpret: bool = False):
    """Returns fn(xr, xi, *filter_args) -> (yr, yi): the WHOLE multi-axis
    spectral pipeline as one pallas_call.

    x is a (batch, na, nr) split re/im float32 scene batch; filter_args
    are the per-segment payloads in segment order, each in kernel layout
    (see :func:`_seg_filter_shapes` — the `ops.mega_spectral_op` wrapper
    handles scene-coordinate reshapes and batching sugar).

    residency RESIDENT_VMEM  : grid over batch blocks, whole (Bb, na, nr)
      slab in VMEM per step, zero HBM intermediates.
    residency RESIDENT_STAGED: grid (batch, phase steps), manual
      double-buffered DMA against an HBM scratch intermediate (see
      :func:`_mega_kernel_staged`).
    """
    check_precision(spec.precision, interpret)
    for seg in spec.segments:
        check_length(spec.seg_spec(seg))
    na, nr = spec.na, spec.nr
    const_plan = _mega_const_plan(spec)
    const_arrays = [jnp.asarray(c) for _, cs in const_plan for c in cs]
    x_shape = (batch, na, nr)
    out_shape = [
        jax.ShapeDtypeStruct(x_shape, jnp.float32),
        jax.ShapeDtypeStruct(x_shape, jnp.float32),
    ]

    if spec.residency == RESIDENT_VMEM:
        bb = spec.batch_block or 1
        if batch % bb:
            raise ValueError(
                f"batch={batch} not divisible by batch_block={bb}")
        x_spec = pl.BlockSpec((bb, na, nr), lambda b: (b, 0, 0))
        in_specs = [x_spec, x_spec]
        in_specs += [pl.BlockSpec(c.shape, lambda b: (0, 0))
                     for c in const_arrays]
        for seg in spec.segments:
            in_specs += [pl.BlockSpec(shape, lambda b: (0, 0))
                         for shape in _seg_filter_shapes(spec, seg)]
        call = pl.pallas_call(
            functools.partial(_mega_kernel_resident, spec),
            grid=(batch // bb,),
            in_specs=in_specs,
            out_specs=[x_spec, x_spec],
            out_shape=out_shape,
            compiler_params=compiler_params(mega_vmem_bytes(spec, bb),
                                            interpret),
            interpret=interpret,
            name="mega_vmem",
        )
    else:
        phases, steps = _staged_phases(spec)
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        in_specs = [any_spec, any_spec]
        in_specs += [pl.BlockSpec(c.shape, lambda b, s: (0, 0))
                     for c in const_arrays]
        for seg in spec.segments:
            if seg.filter_mode == FILTER_FULL:
                in_specs += [any_spec, any_spec]
            else:
                in_specs += [pl.BlockSpec(shape, lambda b, s: (0, 0))
                             for shape in _seg_filter_shapes(spec, seg)]
        # the corner-turn intermediate lives in HBM; Mosaic allocates
        # scratch only in VMEM/SMEM, so it rides as two extra outputs
        # that the wrapper drops
        out_shape += [jax.ShapeDtypeStruct((na, nr), jnp.float32)] * 2
        call = pl.pallas_call(
            functools.partial(_mega_kernel_staged, spec),
            grid=(batch, steps),
            in_specs=in_specs,
            out_specs=[any_spec] * 4,
            out_shape=out_shape,
            scratch_shapes=_staged_scratch(spec, phases),
            compiler_params=compiler_params(
                mega_vmem_bytes(spec), interpret,
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="mega_staged",
        )

    def fn(xr, xi, *filter_args):
        yr, yi = call(xr, xi, *const_arrays, *filter_args)[:2]
        return yr, yi

    fn.flops = _mega_flops(spec) * batch  # nominal, for benches
    return fn


def _staged_scratch(spec: MegaSpec, phases: list) -> list:
    """VMEM scratch of the staged megakernel, in kernel ref order: the
    row and/or column line slabs, FULL-filter slabs where a phase needs
    them, the bs16 exponent state, and the DMA semaphores."""
    na, nr, depth = spec.na, spec.nr, spec.buffer_depth
    pb_r = next((p["pb"] for p in phases if p["axis"] == 1), None)
    pb_c = next((p["pb"] for p in phases if p["axis"] == 0), None)
    scratch = []
    if pb_r is not None:
        scratch.append(pltpu.VMEM((depth, 2, pb_r, nr), jnp.float32))
    if pb_c is not None:
        scratch.append(pltpu.VMEM((depth, 2, na, pb_c), jnp.float32))
    if any(p["axis"] == 1 and p["seg"].filter_mode == FILTER_FULL
           for p in phases):
        scratch.append(pltpu.VMEM((depth, 2, pb_r, nr), jnp.float32))
    if any(p["axis"] == 0 and p["seg"].filter_mode == FILTER_FULL
           for p in phases):
        scratch.append(pltpu.VMEM((depth, 2, na, pb_c), jnp.float32))
    if PRECISIONS[spec.precision].block_scaled:
        # bs16 carried-exponent state: per-row and per-col exponent
        # vectors persisting across the sequential phase steps, so
        # the HBM scratch stays scaled end to end (_mega_kernel_staged)
        scratch.append(pltpu.VMEM((na, 1), jnp.float32))
        scratch.append(pltpu.VMEM((1, nr), jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((depth, 6)))
    return scratch


def mega_vmem_bytes(spec: MegaSpec, batch_block: int = 1,
                    devices: int = 1) -> int:
    """Estimated VMEM of one megakernel grid step (same accounting as
    :func:`spectral_vmem_bytes`). VMEM-resident: the whole (Bb, na, nr)
    slab double-buffered in and out plus whole-scene temporaries — 1/P
    of its lines per device when sharded over ``devices``. Staged: the
    line-slab scratch plus one phase block's temporaries."""
    na, nr = spec.na, spec.nr
    total = 2 * sum(tile_bytes(c.shape) for _, cs in _mega_const_plan(spec)
                    for c in cs)
    if spec.residency == RESIDENT_VMEM:
        scene = tile_bytes((max(1, na // devices), nr))
        total += 8 * batch_block * scene + 4 * batch_block * scene
        total += _TEMP_SLABS * scene
        total += 2 * sum(tile_bytes(s) for seg in spec.segments
                         for s in _seg_filter_shapes(spec, seg))
        return total
    phases, _ = _staged_phases(spec)
    for seg in spec.segments:
        if seg.filter_mode != FILTER_FULL:
            total += 2 * sum(tile_bytes(s)
                             for s in _seg_filter_shapes(spec, seg))
    for buf in _staged_scratch(spec, phases):
        if buf.memory_space == pltpu.VMEM:
            total += tile_bytes(buf.shape)
    total += max((_TEMP_SLABS + 4) * tile_bytes(
        (p["pb"], nr) if p["axis"] == 1 else (na, p["pb"])) for p in phases)
    return total
