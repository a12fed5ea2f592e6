"""Multi-device SAR: shard_map RDA with corner-turn collectives.

A SAR scene alternates between row-local (range) and column-local (azimuth)
stages, so the classic multi-node schedule is a "corner turn" — an all-to-all
that re-shards the matrix from azimuth-sharded to range-sharded. Two
schedules are provided (the collective-bytes trade-off is a §Perf experiment):

``corner2``  The 3-dispatch RDA (rda.build_fused3) distributed directly:
             azimuth stages run on column slabs, the fused range stage on row
             slabs, with a corner turn before and after it. 2 all-to-alls,
             every compute stage a single fused Pallas dispatch.

``halo``     The paper-ordered pipeline with ONE corner turn: range
             compression is row-local on the natural (azimuth-sharded) raw
             layout; after one corner turn the azimuth FFT + azimuth
             compression are column-local, and RCMC (which gathers at most
             `halo` range cells across the cut) uses a halo exchange with the
             two ring neighbours (collective_permute) instead of a second
             all-to-all. all_to_all bytes halve; permute bytes are
             O(halo/nr_local) of a corner turn.

Beyond the two hand-written schedules, `lower_pipeline` lowers ANY
transpose-free compiled plan — including the single-dispatch megakernel
family (fused1 / csa_fused1 / omegak_fused1): a mega step splits at its
in-kernel corner-turn boundaries into per-device segment groups, one
megakernel dispatch per device per group, with the turns between groups
becoming the all_to_alls (docs/distributed.md §Mega lowering).

Both return the focused image range-sharded (na, nr/P). Ingest layouts differ
(each matches a physically sensible way to distribute arriving pulses):
  corner2: raw sharded P(None, axes) — each pulse scattered across devices
           (range-sharded ingest; azimuth stages are then immediately local)
  halo:    raw sharded P(axes, None) — pulses round-robined across devices
           (pulse-sharded ingest; range compression is immediately local)
  output image (na, nr) sharded P(None, axes) — range columns distributed
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.sar import filters
from repro.kernels.fft4step import (
    FILTER_FULL,
    FILTER_NONE,
    FILTER_OUTER,
    FILTER_SHARED,
    FILTER_SHARED_OUTER,
    default_line_block as _line_block,
    resolve_precision,
)
from repro.core.sar.geometry import SceneConfig
from repro.core.sar.rda import split, unsplit
from repro.kernels import ops


def _axis_size(mesh: Mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def make_sar_mesh(axes=("data",), devices=None) -> Mesh:
    """A corner-turn-friendly mesh over every visible device, multi-host
    capable.

    Devices sort by ``(process_index, id)`` so each host owns a CONTIGUOUS
    block of the sharded axis (the corner2 layout): a corner-turn
    all_to_all then moves the bulk of its (P-1)/P payload between
    neighbouring slabs on the same host's links, and only the slab
    fraction crossing a host boundary rides the network. With two axis
    names the mesh is processes x local-devices (e.g. ``("pod", "data")``
    for per-host sharding with a pod axis for data parallelism); with one
    it is the flat 1-D mesh every single-host path uses today.
    """
    if isinstance(axes, str):
        axes = (axes,)
    if devices is None:
        devices = sorted(jax.devices(),
                         key=lambda d: (d.process_index, d.id))
    devs = np.asarray(devices, dtype=object)
    if len(axes) == 1:
        return Mesh(devs, axes)
    if len(axes) == 2:
        nproc = len({d.process_index for d in devices})
        if nproc == 0 or len(devices) % nproc:
            raise ValueError(
                f"{len(devices)} devices do not tile {nproc} processes")
        return Mesh(devs.reshape(nproc, -1), axes)
    raise ValueError(f"make_sar_mesh supports 1 or 2 axis names, got "
                     f"{axes!r}")


# ---------------------------------------------------------------------------
# Schedule 1: two corner turns around the fused range stage
# ---------------------------------------------------------------------------

def build_corner2(cfg: SceneConfig, mesh: Mesh, axes=("data",),
                  interpret: Optional[bool] = None, block: int = 8,
                  col_block: int = 8, fft_impl: str = "matmul",
                  turn_dtype=None):
    """Returns jit-able fn(raw (na, nr) complex64) -> image, both sharded.

    turn_dtype: optional dtype for the corner-turn payload (e.g.
    jnp.bfloat16) — halves the dominant collective term; quality impact is
    measured in tests (§Perf-SAR iteration 3)."""
    p = _axis_size(mesh, axes)
    if cfg.nr % p or cfg.na % p:
        raise ValueError(f"scene {cfg.na}x{cfg.nr} not divisible by {p} devices")

    hr_r, hr_i = (jnp.asarray(a) for a in filters.range_matched_filter(cfg))
    rc_u, rc_v = (jnp.asarray(a) for a in filters.rcmc_phase_uv(cfg))
    az_u2, az_v2 = (jnp.asarray(a) for a in filters.azimuth_phase_uv2(cfg))
    rkw = dict(interpret=interpret, block=block, fft_impl=fft_impl)
    ckw = dict(interpret=interpret, block=col_block, fft_impl=fft_impl)

    def turn(x, split_axis, concat_axis):
        dt = x.dtype
        if turn_dtype is not None:
            # bf16 wire format for the turn: the FFT magnitudes are
            # O(sqrt(N)) and bf16's 8-bit mantissa costs ~2e-3 relative —
            # validated acceptable for imaging (SNR delta < 0.01 dB). The
            # optimization_barrier pins the converts to the collective's two
            # sides so XLA cannot re-widen the payload.
            x = jax.lax.optimization_barrier(x.astype(turn_dtype))
        x = jax.lax.all_to_all(x, axes, split_axis, concat_axis, tiled=True)
        if turn_dtype is not None:
            x = jax.lax.optimization_barrier(x)
        return x.astype(dt)

    def local(xr, xi, rc_u_blk, az_u2_blk):
        # in: (na, nr/P) column slab; azimuth lines complete per column.
        xr, xi = ops.fft_cols(xr, xi, **ckw)                 # dispatch 1
        # corner turn -> (na/P, nr) row slab (rows = azimuth freq)
        xr = turn(xr, 0, 1)
        xi = turn(xi, 0, 1)
        xr, xi = ops.fused_rc_rcmc_rows(
            xr, xi, hr_r, hr_i, rc_u_blk, rc_v, **rkw)       # dispatch 2
        # corner turn back -> (na, nr/P)
        xr = turn(xr, 1, 0)
        xi = turn(xi, 1, 0)
        xr, xi = ops.fused_mult_ifft_cols_outer(
            xr, xi, az_u2_blk, az_v2, **ckw)                 # dispatch 3
        return xr, xi

    shard = functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, axes), P(None, axes), P(axes), P(axes, None)),
        out_specs=(P(None, axes), P(None, axes)), check_vma=False)

    @jax.jit
    def run(raw):
        xr, xi = split(raw)
        # rc_u is per azimuth-frequency row -> sharded with the row slabs;
        # az_u2 is per range gate -> sharded with the column slabs.
        yr, yi = shard(local)(xr, xi, rc_u, az_u2)
        return unsplit(yr, yi)

    return run


# ---------------------------------------------------------------------------
# Schedule 2: one corner turn + halo-exchange RCMC
# ---------------------------------------------------------------------------

def _halo_rcmc(xr, xi, cfg: SceneConfig, axes, halo: int, p: int,
               taps: int = 8):
    """Sinc-interp RCMC on an (na, nr/P) column slab with ring halo exchange.

    Every row's shift is <= halo - taps//2 cells, so each device only needs
    `halo` columns from its right neighbour (shifts are non-negative: the
    migration curve always moves content to larger range).
    """
    s = jnp.asarray(filters.rcmc_shift_samples(cfg), jnp.float32)[:, None]
    base = jnp.floor(s)
    frac = s - base
    offs = np.arange(taps) - taps // 2 + 1
    xk = offs[None, None, :] - frac[..., None]
    w = jnp.sinc(xk) * jnp.where(
        jnp.abs(xk) <= taps // 2,
        0.54 + 0.46 * jnp.cos(np.pi * xk / (taps // 2)), 0.0)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    # halo exchange with both ring neighbours (the shift is non-negative, but
    # the sinc taps reach taps//2 - 1 cells to the left). p is the static
    # device count along `axes` (jax.lax.axis_size is newer-jax-only).
    lh = taps // 2
    perm_r = [((i + 1) % p, i) for i in range(p)]  # right neighbour -> me
    perm_l = [((i - 1) % p, i) for i in range(p)]  # left neighbour -> me

    def with_halo(x):
        from_right = jax.lax.ppermute(x[:, :halo], axes, perm_r)
        from_left = jax.lax.ppermute(x[:, -lh:], axes, perm_l)
        return jnp.concatenate([from_left, x, from_right], axis=1)

    hxr, hxi = with_halo(xr), with_halo(xi)
    nr_loc = xr.shape[1]
    cols = jnp.arange(nr_loc, dtype=jnp.int32)[None, :]
    yr = jnp.zeros_like(xr)
    yi = jnp.zeros_like(xi)
    for k in range(taps):
        idx = jnp.clip(cols + lh + base.astype(jnp.int32) + offs[k], 0,
                       nr_loc + lh + halo - 1)
        wk = w[..., k]
        yr = yr + jnp.take_along_axis(hxr, jnp.broadcast_to(idx, xr.shape), 1) * wk
        yi = yi + jnp.take_along_axis(hxi, jnp.broadcast_to(idx, xi.shape), 1) * wk
    return yr, yi


def build_halo(cfg: SceneConfig, mesh: Mesh, axes=("data",),
               interpret: Optional[bool] = None, block: int = 8,
               col_block: int = 8, fft_impl: str = "matmul",
               halo: Optional[int] = None):
    p = _axis_size(mesh, axes)
    if cfg.nr % p or cfg.na % p:
        raise ValueError(f"scene {cfg.na}x{cfg.nr} not divisible by {p} devices")
    max_shift = float(np.max(filters.rcmc_shift_samples(cfg)))
    halo = halo or int(np.ceil(max_shift)) + 8
    if halo > cfg.nr // p:
        # the halo premise (halo << nr/P) fails: each device would need more
        # than its whole neighbour slab, i.e. the exchange degenerates to a
        # corner turn. Applicability bound recorded in EXPERIMENTS.md §Perf.
        raise ValueError("halo exceeds local slab width; use corner2")

    hr_r, hr_i = (jnp.asarray(a) for a in filters.range_matched_filter(cfg))
    az_u2, az_v2 = (jnp.asarray(a) for a in filters.azimuth_phase_uv2(cfg))
    rkw = dict(interpret=interpret, block=block, fft_impl=fft_impl)
    ckw = dict(interpret=interpret, block=col_block, fft_impl=fft_impl)

    def local(xr, xi, az_u2_blk):
        # in: (na/P, nr) row slab — the raw data's natural layout.
        xr, xi = ops.fused_fft_mult_ifft_rows(xr, xi, hr_r, hr_i, **rkw)  # 1
        # the single corner turn -> (na, nr/P)
        xr = jax.lax.all_to_all(xr, axes, 1, 0, tiled=True)
        xi = jax.lax.all_to_all(xi, axes, 1, 0, tiled=True)
        xr, xi = ops.fft_cols(xr, xi, **ckw)                              # 2
        xr, xi = _halo_rcmc(xr, xi, cfg, axes, halo, p)                   # 3
        xr, xi = ops.fused_mult_ifft_cols_outer(
            xr, xi, az_u2_blk, az_v2, **ckw)                              # 4
        return xr, xi

    shard = functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes)),
        out_specs=(P(None, axes), P(None, axes)), check_vma=False)

    @jax.jit
    def run(raw):
        xr, xi = split(raw)
        yr, yi = shard(local)(xr, xi, az_u2)
        return unsplit(yr, yi)

    return run


# ---------------------------------------------------------------------------
# Generic corner-turn lowering of a compiled SpectralPlan pipeline
# ---------------------------------------------------------------------------
#
# Every fused spectral dispatch processes line blocks independently — that
# is what lets the streaming executor strip a scene through host memory.
# The same property lets a compiled pipeline shard: each step runs on the
# slab sharded along its free (line) axis, and wherever two consecutive
# steps transform different axes the lowering inserts a corner turn
# (all_to_all). Line-indexed filter payloads (FULL matrices, OUTER u
# vectors) enter shard_map with the matching PartitionSpec so every device
# sees exactly its slab's slice; shared vectors and outer v factors ride
# along replicated. For the 3-dispatch RDA this reproduces the
# hand-written `corner2` schedule bit-for-bit (tests/test_distributed.py).

def _spec_for_filter(name: str, arr, mode: str, stream_axis: int, axes):
    """PartitionSpec for one filter operand in scene orientation."""
    if name in ("hr", "hi"):
        if mode == FILTER_FULL and arr.ndim == 2:
            return P(axes, None) if stream_axis == 0 else P(None, axes)
        return P(None)                     # shared (n,) vector: replicated
    if name == "u":                        # (lines, K): lines = stream axis
        return P(axes, None)
    return P(*([None] * arr.ndim))         # v (n, K): replicated


def _lowerable_steps(pipe) -> list:
    steps = list(pipe.steps)
    if not steps:
        raise ValueError(f"pipeline {pipe.name!r} has no steps")
    for s in steps:
        if s.kind == "mega":
            if s.kernel_kw is None or s.seg_filter_args is None:
                raise ValueError(
                    f"mega step {s.name!r} carries no per-segment filter "
                    "payloads (seg_filter_args) — it was compiled by a "
                    "pre-sharding build; recompile the plan (e.g. "
                    "core.plan.compile_plan / cached_pipeline) and lower "
                    "the fresh pipeline")
            continue
        if (s.kind != "spectral" or s.stream_axis is None
                or s.kernel_kw is None):
            raise ValueError(
                f"step {s.name!r} (kind {s.kind!r}) cannot lower to "
                "shard_map slabs: a transpose/custom stage reorders the "
                "whole scene, which no per-device slab can do locally. "
                "Compile a transpose-free per-axis variant (fused3 / "
                "csa_fused / omegak), or their single-dispatch megakernel "
                "twins (fused1 / csa_fused1 / omegak_fused1, "
                "fuse=FUSE_MEGA) whose in-kernel corner turns lower to "
                "all_to_all collectives; transposing variants run locally "
                "via Pipeline.run / run_streamed instead")
    return steps


def _clamped_block(kernel_kw: dict, lines_local: int) -> dict:
    """The per-dispatch line block must fit (and divide) the local slab."""
    kw = dict(kernel_kw)
    blk = min(int(kw.get("block") or _line_block()), lines_local)
    while lines_local % blk:
        blk -= 1
    kw["block"] = max(1, blk)
    return kw


def _divisor_block(want: int, lines: int) -> int:
    """Largest block <= want that divides lines (>= 1)."""
    blk = min(int(want), int(lines))
    while lines % blk:
        blk -= 1
    return max(1, blk)


def _mega_groups(step):
    """Split a mega step's in-kernel segment chain at its corner-turn
    boundaries: consecutive same-axis segment records (with their
    scene-coordinate filter payloads) form one per-device group — one
    staged megakernel dispatch per device, the turns BETWEEN groups
    becoming all_to_all collectives. Returns
    ``[(axis, [records], [per-seg farg tuples]), ...]``."""
    recs = step.kernel_kw["segments"]
    fargs = step.seg_filter_args
    if len(recs) != len(fargs):
        raise ValueError(
            f"mega step {step.name!r}: {len(recs)} segment records but "
            f"{len(fargs)} per-segment filter payloads")
    groups: list = []
    for rec, fa in zip(recs, fargs):
        axis = rec[0]
        if groups and groups[-1][0] == axis:
            groups[-1][1].append(rec)
            groups[-1][2].append(tuple(fa))
        else:
            groups.append((axis, [rec], [tuple(fa)]))
    return groups


def _mega_filter_specs(mode: str, arrays, stream_axis: int, axes) -> list:
    """PartitionSpecs for one mega segment's scene-coordinate payload.

    The free (line) axis is the sharded one: FULL 2-D filters and OUTER
    ``u`` factors slice with the slab; SHARED vectors (the complete
    transform axis) and OUTER ``v`` factors replicate."""
    def line_sharded(a):
        if a.ndim != 2:
            return P(None)
        return P(axes, None) if stream_axis == 0 else P(None, axes)

    specs: list = []
    arrays = list(arrays)
    if mode in (FILTER_SHARED, FILTER_FULL, FILTER_SHARED_OUTER):
        hr, hi = arrays[0], arrays[1]
        for a in (hr, hi):
            # SHARED payloads are 1-D (whole transform axis, replicated);
            # a 2-D payload is a FULL scene-shaped filter, sliced like x
            specs.append(line_sharded(a) if mode != FILTER_SHARED
                         else P(None))
    if mode in (FILTER_OUTER, FILTER_SHARED_OUTER):
        u, v = arrays[-2], arrays[-1]
        # u is (lines, K) — lines IS the sharded free axis; v is (n, K)
        # on the complete transform axis
        specs.append(P(axes, *([None] * (u.ndim - 1))))
        specs.append(P(*([None] * v.ndim)))
    if mode == FILTER_NONE and arrays:
        raise ValueError("filter-less segment carries payload arrays")
    return specs


# kernel knobs a mega step's kernel_kw shares with every per-device group
_MEGA_GROUP_KW = ("fft_impl", "interpret", "precision", "karatsuba",
                  "buffer_depth")


def _group_mega_kw(src: dict, recs, stream_axis: int, lines_local: int,
                   na_local: int, nr_local: int, filter_bytes: int,
                   residency: Optional[str]) -> dict:
    """The `ops.mega_spectral_op` kwargs for ONE per-device segment
    group: the parent dispatch's global knobs, the group's own segment
    records, a phase_block clamped to divide the LOCAL free-axis lines,
    and the residency re-resolved for the 1/P slab (unless pinned)."""
    kw = {k: src[k] for k in _MEGA_GROUP_KW if k in src}
    kw["segments"] = tuple(recs)
    if stream_axis == 0:
        # row slab (na/P, nr): the global n1/n2/n3 range-axis override
        # still factors this slab's full-width range axis. Column slabs
        # slice the range axis, so a full-width factorization would no
        # longer multiply out — axis-0 groups fall back to the default
        # split (per-segment 8-field records stay valid either way: they
        # factor the transform axis, which sharding never slices).
        for k in ("n1", "n2", "n3"):
            kw[k] = src.get(k)
    kw["phase_block"] = _divisor_block(src.get("phase_block") or _line_block(),
                                       lines_local)
    if residency is None:
        from repro import tuning
        residency = tuning.cost.mega_residency(
            na_local, nr_local, precision=src.get("precision"),
            filter_bytes=filter_bytes)
    kw["residency"] = residency
    return kw


def lower_pipeline(pipe, mesh: Mesh, axes=("data",), turn_dtype=None,
                   residency: Optional[str] = None):
    """Lower a compiled :class:`~repro.core.plan.Pipeline` onto `mesh`.

    Returns a jit-ed ``fn(raw) -> image`` accepting one scene ``(na, nr)``
    or a batch ``(B, na, nr)``, complex64. The input arrives sharded along
    the FIRST unit's line axis and the image leaves sharded along the
    LAST unit's line axis (for the RDA family both are
    ``P(None, axes)`` — range columns distributed, matching `corner2`).

    Spectral steps lower one-to-one: each runs `ops.spectral_op` on the
    slab sharded along its free (line) axis. A MEGA step is split at its
    in-kernel corner-turn boundaries into per-device segment groups
    (range segments on range-sharded ``(na/P, nr)`` slabs, azimuth
    segments on ``(na, nr/P)``): each group is ONE
    `ops.mega_spectral_op` megakernel dispatch per device — zero HBM
    intermediates within the group — and the in-kernel turns between
    groups become the all_to_alls. ``residency`` pins every group's mode
    ('vmem' | 'staged'); the default re-resolves per group on the 1/P
    local slab (`repro.tuning.cost.mega_residency`), so a 4096² scene
    that must stage locally can run VMEM-resident per device.

    Collective cost: one all_to_all of the full scene per axis change
    (2 · 8 · na · nr · (P−1)/P bytes each for split float32 re/im, halved
    by ``turn_dtype=jnp.bfloat16``; `tuning.cost.collective_turn_bytes` /
    `turn_seconds` price exactly this). Block-scaled (bs16) mega chains
    keep the slab SCALED on the wire and all_gather the carried per-line
    exponent vector alongside it (4 · lines · (P−1)/P bytes per turn —
    the same cost functions price it via their ``precision`` argument),
    then unscale after the turn: since power-of-two scaling is exact, the
    sharded bs16 image is bit-identical to the local megakernel's (the
    exponent of a line never depends on how the free axis was sharded).
    A K-unit lowering has at most
    K−1 turns; fused3/csa_fused/omegak AND the fused1 megakernel family
    all have exactly 2 — the `corner2` schedule generalized to any plan
    the compiler accepts.

    The returned runner carries the lowering's shape as attributes:
    ``devices``, ``dispatches_per_device`` (units), ``turns``
    (collective corner turns), and ``unit_info`` (name / stream axis /
    kind / residency per unit) — the compiler dispatch-count invariant
    benchmarks and tests assert.
    """
    p = _axis_size(mesh, axes)
    cfg = pipe.cfg
    steps = _lowerable_steps(pipe)

    # ---- flatten steps into UNITS: one shard_map-local dispatch each ----
    farg_arrays: list = []
    farg_specs: list = []
    units: list = []   # (stream_axis, label, kind, residency, carry, apply)

    def add_spectral(s):
        names = sorted((s.filter_kw or {}).keys())
        start = len(farg_arrays)
        for name in names:
            arr = s.filter_kw[name]
            farg_arrays.append(arr)
            farg_specs.append(_spec_for_filter(name, arr, s.filter_mode,
                                               s.stream_axis, axes))
        lines_local = (cfg.na if s.stream_axis == 0 else cfg.nr) // p
        kw = _clamped_block(s.kernel_kw, lines_local)

        def apply(xr, xi, fargs, _names=tuple(names), _kw=kw, _i=start):
            fk = {n: fargs[_i + j] for j, n in enumerate(_names)}
            return ops.spectral_op(xr, xi, **fk, **_kw)

        units.append((s.stream_axis, s.name, "spectral", None, False,
                      apply))

    def add_mega(s):
        for gi, (axis, recs, seg_fargs) in enumerate(_mega_groups(s)):
            stream = 1 - axis
            lines_local = (cfg.na if stream == 0 else cfg.nr) // p
            start = len(farg_arrays)
            fbytes = 0
            for rec, fa in zip(recs, seg_fargs):
                mode = rec[3]
                specs = _mega_filter_specs(mode, fa, stream, axes)
                if len(specs) != len(fa):
                    raise ValueError(
                        f"mega step {s.name!r} group {gi}: segment mode "
                        f"{mode!r} expects {len(specs)} payload arrays, "
                        f"got {len(fa)}")
                farg_arrays.extend(fa)
                farg_specs.extend(specs)
                fbytes += sum(int(np.prod(a.shape)) * 4 // p for a in fa)
            count = len(farg_arrays) - start
            na_l = cfg.na // p if stream == 0 else cfg.na
            nr_l = cfg.nr if stream == 0 else cfg.nr // p
            kw = _group_mega_kw(s.kernel_kw, recs, stream, lines_local,
                                na_l, nr_l, fbytes, residency)
            # block-scaled groups chain their carried per-line exponents
            # through the turns (ops.mega_spectral_op exp_in/return_exp)
            carry = resolve_precision(kw.get("precision")).block_scaled

            def apply(xr, xi, fargs, exp_in=None, return_exp=False,
                      _kw=kw, _i=start, _c=count):
                return ops.mega_spectral_op(
                    xr, xi, *fargs[_i:_i + _c], exp_in=exp_in,
                    return_exp=return_exp, **_kw)

            units.append((stream, f"{s.name}[g{gi}]", "mega",
                          kw["residency"], carry, apply))

    for s in steps:
        (add_mega if s.kind == "mega" else add_spectral)(s)

    for stream, label, _kind, _res, _carry, _apply in units:
        lines = cfg.na if stream == 0 else cfg.nr
        if lines % p:
            raise ValueError(
                f"unit {label!r}: {lines} lines not divisible by {p} "
                "devices")

    n_turns = sum(1 for a, b in zip(units, units[1:]) if a[0] != b[0])

    def _turn(x, from_axis: int, bpre: int):
        # re-shard: sharded rows -> sharded cols (or back). split/concat in
        # local coordinates, offset past any batch dims.
        split_axis = bpre + (1 - from_axis)
        concat_axis = bpre + from_axis
        dt = x.dtype
        if turn_dtype is not None:
            # narrow wire format; barriers pin the converts to the
            # collective (see build_corner2.turn)
            x = jax.lax.optimization_barrier(x.astype(turn_dtype))
        x = jax.lax.all_to_all(x, axes, split_axis, concat_axis, tiled=True)
        if turn_dtype is not None:
            x = jax.lax.optimization_barrier(x)
        return x.astype(dt)

    def _build(ndim: int):
        bpre = ndim - 2

        def dspec(stream_axis: int):
            scene = ((axes, None) if stream_axis == 0 else (None, axes))
            return P(*([None] * bpre), *scene)

        def local(xr, xi, *fargs):
            cur = units[0][0]
            exp = None
            for i, (stream, _label, _kind, _res, carry, apply) \
                    in enumerate(units):
                if stream != cur:
                    xr = _turn(xr, cur, bpre)
                    xi = _turn(xi, cur, bpre)
                    if exp is not None:
                        # the carried per-line exponents ride the corner
                        # turn with the (still scaled) slab: they are
                        # sharded along their own line axis — the
                        # PREVIOUS group's stream axis — and after the
                        # turn every device's re-sharded slab spans all
                        # of those lines, so an all_gather restores the
                        # full vector (priced with the turn in
                        # tuning.cost.collective_turn_bytes)
                        exp = jax.lax.all_gather(
                            exp, axes, axis=bpre + cur, tiled=True)
                    cur = stream
                if carry:
                    chain = i + 1 < len(units) and units[i + 1][4]
                    if chain:
                        xr, xi, exp = apply(xr, xi, fargs, exp_in=exp,
                                            return_exp=True)
                    else:
                        xr, xi = apply(xr, xi, fargs, exp_in=exp)
                        exp = None
                else:
                    xr, xi = apply(xr, xi, fargs)
            return xr, xi

        shard = functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(dspec(units[0][0]), dspec(units[0][0]), *farg_specs),
            out_specs=(dspec(units[-1][0]), dspec(units[-1][0])),
            check_vma=False)

        @jax.jit
        def run(raw):
            xr, xi = split(raw)
            yr, yi = shard(local)(xr, xi, *farg_arrays)
            return unsplit(yr, yi)

        return run

    runners: dict[int, callable] = {}

    def run(raw):
        nd = jnp.ndim(raw)
        if nd not in (2, 3):
            raise ValueError("expected (na, nr) or (B, na, nr)")
        if nd not in runners:
            runners[nd] = _build(nd)
        return runners[nd](raw)

    # the lowering's shape, for dispatch-count invariants and BENCH rows
    run.devices = p
    run.dispatches_per_device = len(units)
    run.turns = n_turns
    run.unit_info = tuple(
        {"name": label, "stream_axis": stream, "kind": kind,
         "residency": res, "carries_exponents": carry}
        for stream, label, kind, res, carry, _apply in units)
    return run


def build_sharded(cfg: SceneConfig, variant: str = "fused3",
                  mesh: Optional[Mesh] = None, axes=("data",),
                  schedule: str = "corner2", turn_dtype=None, **compile_kw):
    """Compile `variant` for `cfg` and return a multi-device runner.

    schedule 'corner2': the generic plan lowering (`lower_pipeline`) — an
    all_to_all corner turn at every transform-axis change; works for any
    transpose-free spectral plan and reproduces the hand-written corner2
    schedule exactly on the 3-dispatch RDA. compile_kw (precision, block,
    fft_kw, ...) route to the plan compiler.

    schedule 'halo': the hand-written single-turn RDA schedule
    (`build_halo`) — range compression on the natural pulse-sharded
    layout, ONE corner turn, ring halo-exchange RCMC. RDA only; the
    `variant` argument selects nothing beyond asserting RDA semantics.

    This is the focusing service's `sharded` execution backend
    (repro.service.backends.ShardedBackend).
    """
    if mesh is None:
        mesh = make_sar_mesh(axes)
    if schedule == "halo":
        if variant not in ("fused3", "fused_tfree", "fused", "unfused"):
            raise ValueError(
                f"schedule 'halo' implements the RDA; variant {variant!r} "
                "is not an RDA pipeline (use schedule='corner2')")
        supported = ("interpret", "block", "col_block", "fft_impl", "halo")
        ignored = sorted(set(compile_kw) - set(supported))
        if ignored or turn_dtype is not None:
            # refuse rather than silently run f32/full-width: a client
            # that asked for precision='bf16' must not get an unlabelled
            # f32 result back
            bad = ignored + (["turn_dtype"] if turn_dtype is not None
                             else [])
            raise ValueError(
                f"schedule 'halo' does not support option(s) {bad}; "
                "use schedule='corner2' for precision/turn_dtype")
        return build_halo(cfg, mesh, axes, **compile_kw)
    if schedule != "corner2":
        raise ValueError(f"unknown schedule {schedule!r}; "
                         f"known: corner2, halo")
    from repro.core.sar.rda import build_pipeline
    pipe = build_pipeline(cfg, variant, **compile_kw)
    return lower_pipeline(pipe, mesh, axes=axes, turn_dtype=turn_dtype)


SCHEDULES = {"corner2": build_corner2, "halo": build_halo}


def distributed_focus(raw, cfg: SceneConfig, mesh: Mesh, axes=("data",),
                      schedule: str = "corner2", **kw):
    return SCHEDULES[schedule](cfg, mesh, axes, **kw)(raw)
