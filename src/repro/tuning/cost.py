"""Analytic roofline cost model for fused spectral dispatch candidates.

Ranks :class:`~repro.tuning.space.KernelConfig` candidates WITHOUT running
them, so the measured search (search.py) times only the promising few
instead of the whole space ("Shortest-Path FFT", arXiv 2604.04311: guided
search beats enumeration once the implementation space is large).

The model prices one fused ``[FFT] · H · [IFFT]`` rows dispatch on a
``(batch, lines, n)`` slab as ``max(compute, memory)`` — the roofline —
with three ingredients (formulas in docs/tuning.md):

**Matmul-DFT FLOPs.** Stage ``i`` of the four-step recursion contracts
every length-``n`` line with an ``f_i × f_i`` DFT matrix: ``n · f_i``
complex MACs per line, i.e. ``8 n f_i`` real FLOPs (``6 n f_i`` with
Karatsuba's 3-matmul product). The matrix unit is ``MAX_FACTOR`` wide, so
a factor-``f`` matmul runs at ``(f / MAX_FACTOR) ** 0.5`` of peak (small
operands waste the systolic array; the square root reflects that one of
the two matmul dims — the folded line batch — is already large). Twiddle
and filter pointwise multiplies are priced at the vector unit's rate.
``fft4step._flops_per_line`` (the nominal ``5 n log2 n`` algorithmic
count) is the numerator of the reported efficiency, never the cost — a
matmul FFT does MORE arithmetic than nominal; that is the point.

**Bytes per pass.** The slab is read and written once per dispatch
(``16 n`` bytes per line: split re/im float32 in and out), and every grid
step re-loads the DFT constants (matrices + twiddles) — so a small
``block`` pays the constant traffic ``lines / block`` times. Narrow
matmul operands do not shrink HBM traffic (inputs stay f32; only the
in-VMEM operand cast narrows).

**VMEM feasibility.** A grid step must hold its x/y slabs (double for
the out-of-place stages), the DFT constants, and the filter block inside
the device's VMEM budget (``DeviceSpec.vmem_budget_bytes``, 64 MiB on
v5e) — the TPU analogue of the paper's 32 KiB
threadgroup-memory constraint. Infeasible candidates are cut before
ranking; the cut can never empty a candidate set that contains the
library default (tested).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from repro.kernels import fft4step
from repro.kernels.fft4step import (
    MAX_FACTOR,
    RESIDENT_STAGED,
    RESIDENT_VMEM,
    SpectralSpec,
    _flops_per_line,
    default_factorization,
    resolve_precision,
)
from repro.tuning.space import (
    KernelConfig,
    Schedule,
    ScheduleProblem,
    SegmentConfig,
    SegmentShape,
    TuneKey,
    bucket_batch,
)

@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """What the cost model and the kernel build functions know about one
    device kind. Peaks price the roofline (ranking candidates, never predicting
    a wall time); the VMEM fields size Mosaic limits and residency cuts;
    the rest are per-device defaults the code chooses from."""

    kind: str                 # jax.devices()[0].device_kind
    peak_matmul_flops: float  # f32 (HIGHEST) matrix throughput, FLOP/s
    peak_vpu_flops: float     # pointwise (twiddle/filter) FLOP/s
    peak_hbm_bytes: float     # HBM <-> VMEM bytes/s
    peak_link_bytes: float    # per-chip inter-chip bytes/s
    hbm_bytes: int            # device memory
    vmem_bytes: int           # physical VMEM: the hard cap of any limit
    vmem_budget_bytes: int    # per-grid-step budget of residency cuts
    f16_operands: bool        # the matrix unit takes float16 operands
    serving_tier: str         # the service's default precision tier
    line_block: int           # default rows line block / staged phase block
    source: str


DEVICES = {d.kind: d for d in (
    DeviceSpec(
        kind="TPU v5 lite",
        # 197e12 is the published bf16 peak; an f32 HIGHEST matmul runs
        # as 6 bf16 passes (assumed), so the f32 rate is a sixth of it
        peak_matmul_flops=197e12 / 6,
        peak_vpu_flops=4.0e12,            # assumed, not published
        peak_hbm_bytes=819e9,
        peak_link_bytes=1600e9 / 8,       # 1,600 Gbit/s ICI
        hbm_bytes=16 * 10**9,
        vmem_bytes=128 * 2**20,
        vmem_budget_bytes=64 * 2**20,
        f16_operands=False,               # Mosaic cannot pack f32 to f16
        serving_tier="f32",
        line_block=128,                   # lane-aligned in-VMEM transposes
        source="Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI; "
               "128 MiB VMEM per core (jax pallas tpu_info)"),
    DeviceSpec(
        kind="cpu",
        # the Pallas interpreter on the host: TPU-class magnitudes whose
        # ratios rank candidates, and the v5e VMEM, so interpret-mode
        # runs take the residency routes the chip would
        peak_matmul_flops=2.0e14,
        peak_vpu_flops=4.0e12,
        peak_hbm_bytes=1.2e12,
        peak_link_bytes=5.0e10,
        hbm_bytes=16 * 10**9,
        vmem_bytes=128 * 2**20,
        vmem_budget_bytes=64 * 2**20,
        f16_operands=True,                # interpret mode emulates f16
        serving_tier="bs16",
        line_block=8,                     # no lane alignment to honour
        source="interpret-mode row: nominal constants, not a device"),
)}


def device_spec(kind: Optional[str] = None) -> DeviceSpec:
    """The table row for ``kind`` (default: the first JAX device's kind).
    A device the table does not list is an error, never a default."""
    if kind is None:
        kind = fft4step.device_kind()
    try:
        return DEVICES[kind]
    except KeyError:
        raise KeyError(
            f"device kind {kind!r} is not in repro.tuning.cost.DEVICES "
            f"({sorted(DEVICES)}); add a row with its published peaks"
        ) from None


# Matmul-throughput multiplier per operand precision ("Range, Not
# Precision": narrow operands double matrix-unit throughput; bs16 spends
# a little of it on the block-exponent prologue/epilogue).
_PRECISION_SPEEDUP = {"f32": 1.0, "bf16": 2.0, "f16": 2.0, "bs16": 1.9}


def _factors(config: KernelConfig, n: int) -> tuple:
    return config.factors() or default_factorization(n)


def _legal_split(n: int, fs: tuple) -> bool:
    """``fs`` multiplies to n and every factor lies in [1, MAX_FACTOR]."""
    return math.prod(fs) == n and all(1 <= f <= MAX_FACTOR for f in fs)


def _const_bytes(factors: tuple) -> int:
    """DFT matrices + inter-stage twiddles, split re/im float32 — the
    broadcast operands every grid step re-loads."""
    b = sum(2 * 4 * f * f for f in factors)
    for i in range(len(factors) - 1):
        rest = math.prod(factors[i + 1:])
        b += 2 * 4 * factors[i] * rest
    return b


def vmem_bytes(config: KernelConfig, key: TuneKey) -> int:
    """Per-grid-step VMEM footprint of one fused rows dispatch under
    ``config`` — the kernel's own estimate (fft4step.spectral_vmem_bytes),
    the same number its Mosaic VMEM limit is sized from."""
    fs = _factors(config, key.n) + (None,) * 3
    spec = SpectralSpec(
        n=key.n, fwd=True, inv=True, filter_mode="shared",
        block=config.block or device_spec().line_block,
        n1=fs[0], n2=fs[1], n3=fs[2],
        precision=resolve_precision(config.precision).name)
    return fft4step.spectral_vmem_bytes(spec, batch_block=key.batch)


def structurally_feasible(config: KernelConfig, key: TuneKey) -> bool:
    """Shape legality: the config can build a kernel for ``key`` at all."""
    n = key.n
    if not _legal_split(n, _factors(config, n)):
        return False
    block = config.block or device_spec().line_block
    # ops.spectral_op PADS lines up to a block multiple, so a block that
    # does not divide lines is still runnable (the pad is timed, and
    # priced, honestly); only block > lines is pure waste — the whole
    # dispatch would be mostly padding. Same rule as the legacy sweep.
    if block > key.lines and key.lines % block:
        return False
    return True


def _budget(vmem_budget: Optional[int]) -> int:
    return (device_spec().vmem_budget_bytes if vmem_budget is None
            else vmem_budget)


def feasible(config: KernelConfig, key: TuneKey,
             vmem_budget: Optional[int] = None) -> bool:
    """Structural + footprint feasibility cut (never measured if False)."""
    return structurally_feasible(config, key) and \
        vmem_bytes(config, key) <= _budget(vmem_budget)


def _dispatch_terms(*, n: int, lines: int, batch: int, factors: tuple,
                    karatsuba, precision, transforms: int, filtered: bool,
                    block: int) -> dict:
    """The roofline ingredients of one fused dispatch, itemized.

    This is THE cost kernel: `predicted_seconds` (flat configs), the
    schedule-graph edge weights (`segment_seconds`), and the CLI
    `--explain` breakdown all price through this one function, so a
    schedule edge and the equivalent flat config are costed by
    bit-identical arithmetic."""
    lines_total = batch * lines
    prec = resolve_precision(precision)
    dev = device_spec()
    matmul_rate = dev.peak_matmul_flops * _PRECISION_SPEEDUP[prec.name]

    # compute: per-stage dense-DFT matmuls at factor-dependent efficiency
    mac_flops = 6.0 if karatsuba else 8.0
    matmul = 0.0
    for f in factors:
        util = (f / MAX_FACTOR) ** 0.5
        matmul += transforms * lines_total * mac_flops * n * f / (
            matmul_rate * util)
    # twiddles (one complex multiply per element per stage boundary) and
    # the filter multiply run on the vector unit
    pointwise = transforms * (len(factors) - 1) * 6.0 * n * lines_total
    if filtered:
        pointwise += 6.0 * n * lines_total
    vpu = pointwise / dev.peak_vpu_flops
    compute = matmul + vpu

    # memory: slab in+out once per dispatch, constants once per grid step
    grid_steps = max(1, math.ceil(lines / block))
    bytes_moved = 2 * 2 * 4 * n * lines_total          # x and y, re+im f32
    bytes_moved += grid_steps * _const_bytes(factors)
    if filtered:
        bytes_moved += 2 * 4 * n                       # shared filter
    memory = bytes_moved / dev.peak_hbm_bytes

    return {
        "matmul_seconds": matmul,
        "vpu_seconds": vpu,
        "compute_seconds": compute,
        "bytes_moved": bytes_moved,
        "memory_seconds": memory,
        "predicted_seconds": max(compute, memory) + 0.3 * min(compute,
                                                              memory),
    }


def predicted_seconds(config: KernelConfig, key: TuneKey,
                      fwd: bool = True, inv: bool = True,
                      filtered: bool = True) -> float:
    """Roofline time estimate for one fused dispatch under ``config``.

    Relative ordering is the contract (search.py measures the top of the
    ranking); see the module docstring for the model.
    """
    terms = _dispatch_terms(
        n=key.n, lines=key.lines, batch=key.batch,
        factors=_factors(config, key.n), karatsuba=config.karatsuba,
        precision=config.precision,
        transforms=(1 if fwd else 0) + (1 if inv else 0),
        filtered=filtered, block=config.block or device_spec().line_block)
    return terms["predicted_seconds"]


def cost_breakdown(config: KernelConfig, key: TuneKey,
                   fwd: bool = True, inv: bool = True,
                   filtered: bool = True,
                   vmem_budget: Optional[int] = None) -> dict:
    """The itemized cost-model verdict on one candidate — what the CLI's
    ``--explain`` prints so schedule choices are debuggable: matmul vs
    VPU vs bytes seconds, the roofline total, and both feasibility cuts."""
    terms = _dispatch_terms(
        n=key.n, lines=key.lines, batch=key.batch,
        factors=_factors(config, key.n), karatsuba=config.karatsuba,
        precision=config.precision,
        transforms=(1 if fwd else 0) + (1 if inv else 0),
        filtered=filtered, block=config.block or device_spec().line_block)
    vb = vmem_bytes(config, key)
    terms.update({
        "vmem_bytes": vb,
        "vmem_feasible": vb <= _budget(vmem_budget),
        "structurally_feasible": structurally_feasible(config, key),
    })
    return terms


# ---------------------------------------------------------------------------
# Megakernel (fused1) residency feasibility
# ---------------------------------------------------------------------------
#
# The single-dispatch megakernel has two execution modes and ONE decision:
# does a whole (Bb, na, nr) scene slab — plus both axes' DFT constants and
# the resident filter payloads — fit the device's VMEM budget? If yes, the
# VMEM-resident mode realizes the paper's zero-HBM-intermediate claim; if
# not, the scratch-staged two-phase layout keeps the dispatch count at 1
# while double-buffered DMA hides the corner-turn traffic. This is the
# paper's 32 KiB threadgroup-memory cut, one tier up.

def _mega_spec(na: int, nr: int, shapes, *, residency: str,
               precision=None, phase_block: Optional[int] = None,
               buffer_depth: Optional[int] = None,
               seg_configs=None) -> "fft4step.MegaSpec":
    """A filterless MegaSpec with the given segment shapes: what the
    footprint estimate needs (filters are priced from their bytes)."""
    segs = []
    for i, shape in enumerate(shapes):
        sc = seg_configs[i] if seg_configs is not None else SegmentConfig()
        segs.append(fft4step.SegmentSpec(
            axis=shape.axis, fwd=shape.fwd, inv=shape.inv,
            filter_mode=("none" if shape.fwd or shape.inv else "shared"),
            n1=sc.n1, n2=sc.n2, n3=sc.n3))
    return fft4step.MegaSpec(
        na=na, nr=nr, segments=tuple(segs), residency=residency,
        phase_block=phase_block or device_spec().line_block,
        buffer_depth=buffer_depth or 2,
        precision=resolve_precision(precision).name)


def mega_vmem_bytes(na: int, nr: int, batch_block: int = 1,
                    precision: Optional[str] = None,
                    filter_bytes: int = 0) -> int:
    """VMEM footprint of one VMEM-resident megakernel grid step for the
    canonical azimuth -> range -> azimuth chain (the kernel's estimate,
    fft4step.mega_vmem_bytes), plus the double-buffered filter payloads."""
    spec = _mega_spec(na, nr, _MEGA_SEGMENTS_2D, residency=RESIDENT_VMEM,
                      precision=precision)
    return fft4step.mega_vmem_bytes(spec, batch_block) + 2 * filter_bytes


def mega_residency(na: int, nr: int, batch_block: int = 1,
                   precision: Optional[str] = None, filter_bytes: int = 0,
                   vmem_budget: Optional[int] = None) -> str:
    """The residency mode the compiler picks when none is pinned: VMEM-
    resident iff the whole slab fits the budget, else scratch-staged."""
    fits = mega_vmem_bytes(na, nr, batch_block, precision,
                           filter_bytes) <= _budget(vmem_budget)
    return RESIDENT_VMEM if fits else RESIDENT_STAGED


# ---------------------------------------------------------------------------
# Schedule-graph edge weights
# ---------------------------------------------------------------------------
#
# The schedule DAG (docs/tuning.md §Schedule DAG) layers one node set per
# transform segment; an edge through layer i fixes that segment's
# factorization and complex-product algorithm, and the lane (precision,
# block / residency, phase_block, buffer_depth) is fixed per path. Edge
# weights reuse the SAME roofline terms as `predicted_seconds`
# (`_dispatch_terms`), plus a corner-turn term between segments on
# different axes — zero for a VMEM-resident slab (the turn is a logical
# index remap), HBM round-trip bytes for the scratch-staged tier, scaled
# down when double-buffered DMA overlaps the turn with compute (the
# Radix-8 Stockham two-tier observation, arXiv 2603.27569).

# fraction of the corner-turn HBM traffic left on the critical path when
# depth>=2 double-buffering overlaps DMA with the neighbor segment's DFTs
TURN_OVERLAP = 0.6


def segment_seconds(problem: ScheduleProblem, shape: SegmentShape,
                    seg: SegmentConfig, *, precision=None,
                    karatsuba=None, block: Optional[int] = None,
                    residency: Optional[str] = None,
                    phase_block: Optional[int] = None) -> float:
    """Roofline seconds for ONE schedule-DAG segment edge.

    For a staged megakernel the segment streams its lines through VMEM in
    phase_block blocks (constants re-loaded per step, slab in+out through
    the scratch); for a VMEM-resident one the slab is already on-chip, so
    only the compute terms and one constants load remain."""
    n = problem.seg_n(shape)
    lines = problem.seg_lines(shape)
    fs = seg.factors() or default_factorization(n)
    kara = seg.karatsuba if seg.karatsuba is not None else karatsuba
    transforms = (1 if shape.fwd else 0) + (1 if shape.inv else 0)
    if problem.mega and residency == RESIDENT_VMEM:
        # slab resident: no per-segment HBM slab traffic — price compute
        # plus one constants load (entry/exit slab traffic is charged
        # once per path in schedule_seconds)
        terms = _dispatch_terms(
            n=n, lines=lines, batch=problem.batch, factors=fs,
            karatsuba=kara, precision=precision, transforms=transforms,
            filtered=shape.filtered, block=lines)
        return (terms["compute_seconds"]
                + _const_bytes(fs) / device_spec().peak_hbm_bytes)
    eff_block = phase_block if problem.mega else block
    terms = _dispatch_terms(
        n=n, lines=lines, batch=problem.batch, factors=fs,
        karatsuba=kara, precision=precision, transforms=transforms,
        filtered=shape.filtered, block=eff_block or device_spec().line_block)
    return terms["predicted_seconds"]


def collective_turn_bytes(na: int, nr: int, batch: int = 1,
                          devices: int = 1, elem_bytes: int = 4,
                          precision: Optional[str] = None) -> int:
    """Per-device all_to_all wire bytes of ONE corner turn: each device
    holds a split re/im 1/P slab and keeps 1/P of it, so (P-1)/P of the
    slab crosses links (docs/distributed.md §collective bytes; halve via
    ``turn_dtype=bfloat16`` -> elem_bytes=2).

    A block-scaled ``precision`` (bs16) adds the carried per-line
    exponent vector: one f32 per line of the turned axis, all_gathered
    alongside the slab so every device can unscale its re-sharded slab
    (distributed.lower_pipeline). The turned axis is not known here, so
    the longer scene axis bounds it."""
    p = max(1, devices)
    slab = 2 * elem_bytes * na * nr * batch // p
    wire = slab * (devices - 1) // p
    if resolve_precision(precision).block_scaled:
        wire += 4 * max(na, nr) * batch * (devices - 1) // p
    return wire


def turn_seconds(problem: ScheduleProblem, *,
                 residency: Optional[str] = None,
                 buffer_depth: Optional[int] = None,
                 precision: Optional[str] = None) -> float:
    """The corner-turn edge weight between two segments on different
    axes.

    Local (devices == 1): free for a VMEM-resident slab (logical remap),
    an HBM write+read of the scene for the staged tier — overlapped with
    compute when the DMA is double-buffered (depth >= 2).

    Sharded (devices > 1): every turn is a dispatch-boundary all_to_all
    regardless of residency — each device writes its 1/P slab out, moves
    (P-1)/P of it over inter-chip links, and reads the re-sharded slab
    back. The link term dominates (peak_link_bytes < peak_hbm_bytes);
    with ``buffer_depth >= 2`` the staged megakernel's double-buffered
    DMA phases earn the same TURN_OVERLAP credit as the local tier (the
    collective for block j+1 overlaps block j's DFT matmuls)."""
    if problem.devices > 1:
        p = problem.devices
        slab = 2 * 2 * 4 * problem.na * problem.nr * problem.batch // p
        wire = collective_turn_bytes(problem.na, problem.nr,
                                     problem.batch, p,
                                     precision=precision)
        dev = device_spec()
        secs = slab * 2 / dev.peak_hbm_bytes + wire / dev.peak_link_bytes
        overlap = TURN_OVERLAP if (buffer_depth or 2) >= 2 else 1.0
        return secs * overlap
    if residency != RESIDENT_STAGED:
        return 0.0
    traffic = 2 * 2 * 4 * problem.na * problem.nr * problem.batch
    overlap = TURN_OVERLAP if (buffer_depth or 2) >= 2 else 1.0
    return traffic / device_spec().peak_hbm_bytes * overlap


def schedule_vmem_bytes(schedule: Schedule,
                        problem: ScheduleProblem,
                        filter_bytes: int = 0) -> int:
    """Per-grid-step VMEM footprint of a whole schedule.

    Flat problems defer to `vmem_bytes` via the flat-config view. Mega
    problems price the megakernel the schedule would build (one set of DFT
    constants per distinct (axis, factorization), the residency tier's
    slabs or line buffers) with the kernel's own estimate; a sharded
    problem's resident slab holds 1/P of the scene's lines."""
    if not problem.mega:
        key = TuneKey(kind="kernel", backend="-", device="-",
                      n=problem.nr, batch=bucket_batch(problem.batch),
                      lines=problem.na)
        return vmem_bytes(schedule.to_config(), key)
    residency = schedule.residency or RESIDENT_VMEM
    spec = _mega_spec(
        problem.na, problem.nr, problem.segments, residency=residency,
        precision=schedule.precision, phase_block=schedule.phase_block,
        buffer_depth=schedule.buffer_depth,
        seg_configs=[schedule.segment(i)
                     for i in range(len(problem.segments))])
    bb = problem.batch if residency == RESIDENT_VMEM else 1
    return (fft4step.mega_vmem_bytes(spec, bb, devices=problem.devices)
            + 2 * filter_bytes)


def schedule_structurally_feasible(schedule: Schedule,
                                   problem: ScheduleProblem) -> bool:
    """Shape legality of every segment's factorization for its length."""
    for i, shape in enumerate(problem.segments):
        n = problem.seg_n(shape)
        fs = schedule.segment(i).factors() or default_factorization(n)
        if not _legal_split(n, fs):
            return False
    if not problem.mega:
        block = schedule.block or device_spec().line_block
        lines = problem.na
        if block > lines and lines % block:
            return False
    return True


def schedule_feasible(schedule: Schedule, problem: ScheduleProblem,
                      filter_bytes: int = 0,
                      vmem_budget: Optional[int] = None) -> bool:
    """Structural + VMEM feasibility of a complete schedule path."""
    return schedule_structurally_feasible(schedule, problem) and \
        schedule_vmem_bytes(schedule, problem, filter_bytes) <= \
        _budget(vmem_budget)


def schedule_seconds(schedule: Schedule,
                     problem: ScheduleProblem) -> float:
    """Predicted seconds of a complete schedule: the sum of the SAME
    per-segment and per-turn edge weights the graph search accumulates
    (plus, for mega problems, the scene slab's one HBM entry/exit)."""
    total = 0.0
    for i, shape in enumerate(problem.segments):
        total += segment_seconds(
            problem, shape, schedule.segment(i),
            precision=schedule.precision, block=schedule.block,
            residency=schedule.residency,
            phase_block=schedule.phase_block)
    prev = None
    for shape in problem.segments:
        if prev is not None and prev.axis != shape.axis:
            total += turn_seconds(problem, residency=schedule.residency,
                                  buffer_depth=schedule.buffer_depth,
                                  precision=schedule.precision)
        prev = shape
    if problem.mega:
        # the scene enters and leaves HBM exactly once per dispatch —
        # 1/P of it per device when sharded
        slab_io = (2 * 2 * 4 * problem.na * problem.nr * problem.batch
                   / problem.devices)
        total += slab_io / device_spec().peak_hbm_bytes
    return total


# RDA-family megakernel shape (fused1 / csa_fused1 / omegak_fused1 all
# lower to an azimuth -> range -> azimuth segment chain): the canonical
# workload `sharded_preferred` prices when the caller has no plan in hand.
_MEGA_SEGMENTS_2D = (
    SegmentShape(axis=0, fwd=True, inv=False, filtered=False),
    SegmentShape(axis=1, fwd=True, inv=True, filtered=True),
    SegmentShape(axis=0, fwd=False, inv=True, filtered=True),
)


def _default_mega_schedule(na: int, nr: int, devices: int = 1,
                           precision: Optional[str] = None,
                           filter_bytes: int = 0) -> Schedule:
    """The schedule the compiler would pick unprompted: auto residency on
    the (per-device) slab, default phase_block/buffer_depth."""
    res = mega_residency(na // devices if devices > 1 else na, nr,
                         precision=precision, filter_bytes=filter_bytes)
    return Schedule(segments=(SegmentConfig(),) * len(_MEGA_SEGMENTS_2D),
                    precision=precision, residency=res,
                    phase_block=device_spec().line_block,
                    buffer_depth=2)


def sharded_preferred(na: int, nr: int, batch: int = 1, devices: int = 1,
                      precision: Optional[str] = None,
                      filter_bytes: int = 0) -> bool:
    """Whether the roofline prefers the P-device sharded megakernel over
    ONE local dispatch for this scene — the service's big-scene routing
    predicate (`LocalBackend.execute_streamed`).

    Prices the canonical azimuth->range->azimuth megakernel both ways
    with `schedule_seconds`: locally the corner turns are free (VMEM) or
    HBM-priced (staged); sharded they become all_to_all collectives
    (`collective_turn_bytes` over peak_link_bytes) but every compute and
    slab-I/O term divides by P. Scenes whose whole slab fits the local
    VMEM budget never shard — the local single-dispatch megakernel route
    already serves them with zero HBM intermediates, and a collective
    would only add latency; a staged (over-budget) scene shards whenever
    the roofline says P slabs + wire beat one staged device."""
    if devices <= 1 or na % devices or nr % devices:
        return False
    if mega_residency(na, nr, precision=precision,
                      filter_bytes=filter_bytes) == RESIDENT_VMEM:
        return False
    local = ScheduleProblem.mega_2d(na, nr, _MEGA_SEGMENTS_2D, batch=batch)
    shard = ScheduleProblem.mega_2d(na, nr, _MEGA_SEGMENTS_2D, batch=batch,
                                    devices=devices)
    local_s = schedule_seconds(
        _default_mega_schedule(na, nr, 1, precision, filter_bytes), local)
    shard_s = schedule_seconds(
        _default_mega_schedule(na, nr, devices, precision, filter_bytes),
        shard)
    return shard_s < local_s


def serve_batch_seconds(na: int, nr: int, batch: int = 1,
                        precision: Optional[str] = None,
                        streamed: bool = False) -> float:
    """Predicted seconds of ONE served micro-batch — the worker pool's
    lane-routing weight (`repro.service.workers.WorkerPool.route`).

    Prices the canonical azimuth->range->azimuth megakernel (the shape
    every served RDA-family variant lowers to) with `schedule_seconds`,
    at the residency the compiler would pick for the scene — pinned to
    the scratch-staged tier for ``streamed`` keys, whose scenes are over
    the device budget by definition. Relative ordering across keys is
    the contract, exactly as for the kernel search: a 1024² batch must
    weigh a lane's backlog more than a 256² one, by roughly the roofline
    ratio."""
    problem = ScheduleProblem.mega_2d(na, nr, _MEGA_SEGMENTS_2D,
                                      batch=max(1, batch))
    res = (RESIDENT_STAGED if streamed
           else mega_residency(na, nr, precision=precision))
    sched = Schedule(
        segments=(SegmentConfig(),) * len(_MEGA_SEGMENTS_2D),
        precision=precision, residency=res,
        phase_block=device_spec().line_block, buffer_depth=2)
    return schedule_seconds(sched, problem)


def nominal_flops(key: TuneKey, fwd: bool = True, inv: bool = True,
                  filtered: bool = True) -> float:
    """The algorithmic 5 n log2 n count (fft4step._flops_per_line) for the
    whole slab — the numerator of reported efficiency, not the cost."""
    spec = SpectralSpec(
        n=key.n, fwd=fwd, inv=inv,
        filter_mode="shared" if filtered else "none")
    return _flops_per_line(spec) * key.batch * key.lines


def rank(configs, key: TuneKey, vmem_budget: Optional[int] = None,
         **kw) -> list:
    """Feasible configs sorted by predicted cost, cheapest first.

    The VMEM cut must never exclude EVERY candidate (a problem so large
    that no block fits the budget still has to run — smallest footprint
    first, and the measured rungs drop anything the kernel build itself
    rejects): when it would, the cut falls back to structural feasibility
    with the footprint folded into the ordering."""
    feas = [c for c in configs if feasible(c, key, vmem_budget)]
    if feas:
        return sorted(feas, key=lambda c: predicted_seconds(c, key, **kw))
    feas = [c for c in configs if structurally_feasible(c, key)]
    return sorted(feas, key=lambda c: (vmem_bytes(c, key),
                                       predicted_seconds(c, key, **kw)))
