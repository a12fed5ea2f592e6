"""SpectralPlan IR — the SAR focusing chain lifted into data.

The paper's observation is that a whole imaging pipeline is a sequence of
fused ``[FFT] · H · [IFFT]`` stages. This module makes that sequence a
first-class value: a :class:`SpectralPlan` is a tuple of declarative
:class:`Stage` records (axis, fwd/inv, named filter refs, precision), and a
small compiler turns it into executable single-dispatch Pallas calls. RDA,
CSA and ω-K (core/sar/{rda,csa,omegak}.py) are *only* plans — no algorithm
owns an executor loop — so a new algorithm, precision policy, or schedule
is a data change, not a code change (cf. Bergach et al., arXiv 1505.08067,
on modeling the radar stage graph explicitly).

Compiler/executor responsibilities:

* **Fusion** — adjacent compatible stages collapse into one
  ``ops.spectral_op`` dispatch. Stages are flattened to atoms
  (``fft`` / ``mul`` / ``ifft`` / ``transpose`` / custom) and greedily
  regrouped under the kernel grammar ``fft? mul* ifft?`` (same transform
  axis; transposes and custom atoms are barriers). Multiple fused ``mul``
  atoms compose into one kernel filter: shared×shared → shared,
  shared×full → full, outer×outer → rank-(K₁+K₂) outer,
  shared×outer → shared_outer, full×outer → full. ``fuse=FUSE_MEGA``
  additionally fuses ACROSS transform-axis changes — the grammar gains
  in-kernel corner turns, ``fft? mul* ifft? (turn fft? mul* ifft?)*`` —
  collapsing a whole transpose-free plan into ONE megakernel dispatch
  (``ops.mega_spectral_op``; the fused1 pipeline family).
* **Tuning** — per-dispatch :class:`repro.tuning.KernelConfig` records are
  pulled from the repro.tuning cache at compile time (device-fingerprinted,
  batch-bucketed; never re-swept here — ``tune="off"`` skips the lookup
  entirely).
* **Filter caching** — materialized+composed filter tensors are cached per
  ``(SceneConfig, plan, fuse, backend)``, and the underlying host-side
  float64 filter math per ``(SceneConfig, params, filter_name)``, so
  repeated ``focus()`` calls on new scenes skip all host filter work.
* **Streaming** — :meth:`Pipeline.run_streamed` executes the compiled plan
  over strips of a host-resident scene too large for one device buffer:
  each dispatch is re-issued per strip along its free (line) axis with the
  line-indexed filter payloads sliced to match, keeping ≤2 strips in
  flight so strip transfer overlaps compute (jax async dispatch). Because
  the kernel processes line blocks independently, the streamed image is
  bit-identical to the in-memory path.

Filter tensors are *named and lazy*: plans reference filters by string,
the registry maps names to host-side builders, and nothing is materialized
until a plan that uses the name is compiled against a concrete scene.

Plans serialize to/from JSON (``plan_to_json`` / ``plan_from_json``) so a
pipeline definition can be shipped, diffed, and round-tripped.
"""
from __future__ import annotations

import dataclasses
import json
from collections import deque
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.fft4step import (
    FILTER_FULL,
    FILTER_NONE,
    FILTER_OUTER,
    FILTER_SHARED,
    FILTER_SHARED_OUTER,
    default_line_block as _line_block,
    dispatch_scope,
    resolve_factors,
    resolve_precision,
)
from repro.kernels.interleave import interleave as interleave_planes
from repro.kernels.transpose import transpose as tiled_transpose
from repro.tuning import KernelConfig, Schedule, SegmentConfig, cached_config

BACKEND_PALLAS = "pallas"   # fused single-dispatch Pallas kernels
BACKEND_XLA = "xla"         # one jnp op per atom (the unfused oracle)

# Fusion levels accepted by compile_plan/plan_dispatch_count's ``fuse``:
#   False      one dispatch per atom (the unfused oracle grouping)
#   True       per-axis fusion: fft? mul* ifft? on ONE transform axis
#   FUSE_MEGA  cross-axis fusion: fft? mul* ifft? (turn fft? mul* ifft?)*
#              — axis changes become IN-KERNEL corner turns and a whole
#              transpose-free plan collapses to a single megakernel
#              dispatch (kernels/fft4step.build_mega_call)
FUSE_MEGA = "mega"


# Kernels take complex scenes as two f32 planes. The conversions each way
# run under their own scope, so a profile tells this glue apart from the
# kernels wherever it is called (steps, strips, the sharded lowering).

def split(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    with jax.named_scope("split"):
        return (jnp.real(x).astype(jnp.float32),
                jnp.imag(x).astype(jnp.float32))


def unsplit(xr: jnp.ndarray, xi: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("unsplit"):
        return xr.astype(jnp.complex64) + 1j * xi.astype(jnp.complex64)


def interleave(xr: jnp.ndarray, xi: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Two f32 planes ``(..., n)`` -> one f32 array ``(..., 2n)`` whose
    elements alternate real and imaginary part: complex64's own byte
    layout, so its host copy views as complex64 with nothing joined.
    The image's way out of the jitted pipeline, under ``unsplit``."""
    with jax.named_scope("unsplit"):
        return interleave_planes(xr, xi, interpret=interpret)


@jax.jit
def _deinterleave(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.complex(x[..., 0::2], x[..., 1::2])


class FocusedImages:
    """Focused images as :meth:`Pipeline.jitted` hands them back.

    On the device they are one f32 array ``interleaved`` of shape
    ``(..., na, 2·nr)``, real and imaginary parts alternating along range.
    To the host they are complex64 ``(..., na, nr)``: ``np.asarray`` copies
    the f32 array (the runtime's plain relayout) and views it as
    complex64, so the copy back skips the one-thread join of the two
    halves of each complex64 element that a complex64 result costs. The
    copy happens there and nowhere else. :meth:`on_device` (also
    ``jnp.asarray``) gives the complex64 device array, and any other
    ``jax.Array`` attribute (``.at``, ``.conj()``, ...) is read from it."""

    dtype = np.dtype(np.complex64)

    def __init__(self, interleaved: jax.Array):
        self.interleaved = interleaved

    @property
    def shape(self) -> tuple:
        *lead, n2 = self.interleaved.shape
        return (*lead, n2 // 2)

    def block_until_ready(self) -> "FocusedImages":
        self.interleaved.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        img = np.asarray(self.interleaved).view(np.complex64)
        if copy or (dtype is not None and np.dtype(dtype) != img.dtype):
            return np.array(img, dtype=dtype)
        return img

    def on_device(self) -> jax.Array:
        return _deinterleave(self.interleaved)

    __jax_array__ = on_device

    def __getattr__(self, name: str):
        if name.startswith("__"):     # numpy and copy probe for protocols
            raise AttributeError(name)
        return getattr(self.on_device(), name)


# ---------------------------------------------------------------------------
# The IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    """One declarative pipeline stage.

    kind "spectral": ``[FFT if fwd] · filters · [IFFT if inv]`` along
    ``axis`` in scene coordinates (1 = range/rows, 0 = azimuth/columns).
    ``filters`` are registry names (see :func:`register_filter`), applied
    in order; at compile time adjacent filters compose into ONE kernel
    payload (see :func:`_compose_group_filters`). ``precision`` overrides
    the matmul-operand policy for this stage (None defers to the
    compile-time ``precision`` override, then the autotuned config, then
    the library default f32).

    kind "transpose": a global corner turn (fusion barrier). The compiler
    tracks orientation, so stages after a transpose still name their axis
    in scene coordinates.

    Other kinds dispatch to :func:`register_stage_impl` implementations
    (e.g. the sinc-interpolation RCMC), with ``opts`` passed through as a
    plain dict. ``opts`` is stored as a tuple of (key, value) pairs so the
    Stage stays hashable (plans are cache keys).
    """

    name: str
    kind: str = "spectral"
    axis: int = 1
    fwd: bool = False
    inv: bool = False
    filters: tuple[str, ...] = ()
    precision: Optional[str] = None
    opts: tuple[tuple[str, Any], ...] = ()

    def opt_dict(self) -> dict:
        return dict(self.opts)


@dataclasses.dataclass(frozen=True)
class SpectralPlan:
    """A named, hashable sequence of :class:`Stage` records plus static
    plan parameters (e.g. CSA's reference range) that filter builders may
    consume via their ``params`` dict.

    A plan is pure data: it references filters by registry name and never
    holds arrays, so it can be hashed (it keys the compile-time payload
    cache), serialized to JSON (:func:`plan_to_json`), diffed, and
    shipped between processes. Materialization happens only when the plan
    is compiled against a concrete :class:`~repro.core.sar.SceneConfig`
    by :func:`compile_plan`.
    """

    name: str
    stages: tuple[Stage, ...]
    params: tuple[tuple[str, Any], ...] = ()

    def param_dict(self) -> dict:
        return dict(self.params)


# ---------------------------------------------------------------------------
# Serialization
# ---------------------------------------------------------------------------

def plan_to_dict(plan: SpectralPlan) -> dict:
    return {
        "name": plan.name,
        "params": [list(p) for p in plan.params],
        "stages": [
            {
                "name": s.name, "kind": s.kind, "axis": s.axis,
                "fwd": s.fwd, "inv": s.inv, "filters": list(s.filters),
                "precision": s.precision, "opts": [list(o) for o in s.opts],
            }
            for s in plan.stages
        ],
    }


def plan_from_dict(d: dict) -> SpectralPlan:
    stages = tuple(
        Stage(
            name=s["name"], kind=s.get("kind", "spectral"),
            axis=s.get("axis", 1), fwd=s.get("fwd", False),
            inv=s.get("inv", False), filters=tuple(s.get("filters", ())),
            precision=s.get("precision"),
            opts=tuple((k, v) for k, v in s.get("opts", ())),
        )
        for s in d["stages"]
    )
    params = tuple((k, v) for k, v in d.get("params", ()))
    return SpectralPlan(name=d["name"], stages=stages, params=params)


def plan_to_json(plan: SpectralPlan, **kw) -> str:
    return json.dumps(plan_to_dict(plan), **kw)


def plan_from_json(s: str) -> SpectralPlan:
    return plan_from_dict(json.loads(s))


# ---------------------------------------------------------------------------
# Filter registry — named, lazily-materialized filter tensors
# ---------------------------------------------------------------------------
#
# Builders run host-side (numpy, float64 where it matters) and return, per
# mode and in scene coordinates (n = transformed-axis length, lines = the
# other axis):
#   shared: complex vector (n,)
#   full:   complex matrix (na, nr)
#   outer:  (u (lines, K) float32, v (n, K) float32) — phase exp(i Σ u v)

@dataclasses.dataclass(frozen=True)
class FilterDef:
    name: str
    mode: str                      # FILTER_SHARED | FILTER_FULL | FILTER_OUTER
    build: Callable                # (cfg, params: dict) -> arrays


_FILTERS: dict[str, FilterDef] = {}


def register_filter(name: str, mode: str, build: Callable) -> None:
    if mode not in (FILTER_SHARED, FILTER_FULL, FILTER_OUTER):
        raise ValueError(f"unsupported filter mode {mode!r}")
    _FILTERS[name] = FilterDef(name, mode, build)


def filter_names() -> tuple[str, ...]:
    return tuple(sorted(_FILTERS))


# host-side filter-math cache: (cfg, params, name) -> built arrays.
# Bounded FIFO: full 2-D filters are O(scene) host bytes, so a server
# focusing many distinct geometries must not accumulate them forever.
_BUILD_CACHE: dict = {}
_BUILD_CACHE_MAX = 64
_BUILD_STATS = {"hits": 0, "misses": 0}


def _fifo_put(cache: dict, key, value, limit: int) -> None:
    while len(cache) >= limit:
        cache.pop(next(iter(cache)))
    cache[key] = value


def _built(name: str, cfg, params: tuple) -> tuple[str, Any]:
    fd = _FILTERS.get(name)
    if fd is None:
        raise KeyError(f"unknown filter {name!r}; registered: {filter_names()}")
    key = (cfg, params, name)
    if key in _BUILD_CACHE:
        _BUILD_STATS["hits"] += 1
    else:
        _BUILD_STATS["misses"] += 1
        _fifo_put(_BUILD_CACHE, key, fd.build(cfg, dict(params)),
                  _BUILD_CACHE_MAX)
    return fd.mode, _BUILD_CACHE[key]


def filter_cache_stats() -> dict:
    return dict(_BUILD_STATS)


def clear_filter_caches() -> None:
    _BUILD_CACHE.clear()
    _PAYLOAD_CACHE.clear()
    _BUILD_STATS.update(hits=0, misses=0)


# ---------------------------------------------------------------------------
# Custom stage implementations (non-spectral kinds)
# ---------------------------------------------------------------------------
#
# impl(x, cfg, opts, lo, hi) -> x: complex in/out, batch-polymorphic.
# lo/hi select a row range for the streaming executor (None = whole scene);
# stream_axis names the scene axis the stage can be stripped along.

_STAGE_IMPLS: dict[str, tuple[Callable, Optional[int]]] = {}


def register_stage_impl(kind: str, impl: Callable,
                        stream_axis: Optional[int] = 0) -> None:
    _STAGE_IMPLS[kind] = (impl, stream_axis)


# ---------------------------------------------------------------------------
# Stage flattening + fusion grouping
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Atom:
    kind: str                 # "fft" | "ifft" | "mul" | "transpose" | custom
    axis: int                 # scene-coordinate transform/orientation axis
    filter: Optional[str]     # for "mul"
    stage: Stage


def _flatten(plan: SpectralPlan) -> list[_Atom]:
    atoms: list[_Atom] = []
    for s in plan.stages:
        if s.kind == "spectral":
            if s.fwd:
                atoms.append(_Atom("fft", s.axis, None, s))
            for f in s.filters:
                atoms.append(_Atom("mul", s.axis, f, s))
            if s.inv:
                atoms.append(_Atom("ifft", s.axis, None, s))
            if not (s.fwd or s.inv or s.filters):
                raise ValueError(f"empty spectral stage {s.name!r}")
        else:
            atoms.append(_Atom(s.kind, s.axis, None, s))
    return atoms


def _fusable(group: list[_Atom], atom: _Atom, mega: bool = False) -> bool:
    """May `atom` join `group` under the kernel grammar?

    Per-axis (mega=False): fft? mul* ifft? on ONE transform axis —
    transposes and custom kinds never fuse, an ifft closes the group, a
    forward fft only opens one. Cross-axis (mega=True): the grammar gains
    in-kernel corner turns, `fft? mul* ifft? (turn fft? mul* ifft?)*` —
    an axis change always starts a fresh segment (any atom kind may open
    it), while WITHIN the trailing same-axis segment the per-axis rules
    still hold."""
    if atom.kind not in ("fft", "ifft", "mul"):
        return False
    if not group:
        return True
    if group[0].kind not in ("fft", "ifft", "mul"):
        return False
    if atom.axis != group[-1].axis:
        return mega                        # a turn: only the megakernel fuses
    seg = []
    for a in reversed(group):              # the trailing same-axis segment
        if a.axis != atom.axis:
            break
        seg.append(a)
    if any(a.kind == "ifft" for a in seg):
        return False                       # the inverse transform closes a segment
    if atom.kind == "fft":
        return False                       # a forward FFT only opens a segment
    return True


def _group_atoms(atoms: list[_Atom], fuse) -> list[list[_Atom]]:
    if not fuse:
        return [[a] for a in atoms]
    mega = fuse == FUSE_MEGA
    groups: list[list[_Atom]] = []
    cur: list[_Atom] = []
    for a in atoms:
        if cur and _fusable(cur, a, mega):
            cur.append(a)
        else:
            if cur:
                groups.append(cur)
            cur = [a]
    if cur:
        groups.append(cur)
    return groups


def _split_segments(group: list[_Atom]) -> list[list[_Atom]]:
    """A fused group as its per-axis segments (consecutive same-axis
    runs) — one entry for per-axis groups, several for mega groups."""
    segs: list[list[_Atom]] = []
    for a in group:
        if segs and segs[-1][0].axis == a.axis:
            segs[-1].append(a)
        else:
            segs.append([a])
    return segs


def plan_dispatch_count(plan: SpectralPlan, fuse=True) -> int:
    """Dispatches the compiler will emit — the fusion-legality invariant
    tests assert this equals each variant's documented count. ``fuse``
    accepts False / True / :data:`FUSE_MEGA`."""
    return len(_group_atoms(_flatten(plan), fuse))


# ---------------------------------------------------------------------------
# Filter composition (host side, scene coordinates)
# ---------------------------------------------------------------------------

def _compose_group_filters(group: list[_Atom], cfg, params: tuple,
                           axis: int) -> tuple[str, tuple]:
    """Compose the group's mul atoms into ONE kernel filter payload.

    Returns (filter_mode, arrays) in scene coordinates:
      shared       -> (h complex (n,),)
      full         -> (h complex (na, nr),)
      outer        -> (u (lines, K) f32, v (n, K) f32)
      shared_outer -> (h (n,), u, v)
    """
    muls = [a for a in group if a.kind == "mul"]
    if not muls:
        return FILTER_NONE, ()
    shared = None
    full = None
    us, vs = [], []
    for a in muls:
        mode, arrs = _built(a.filter, cfg, params)
        if mode == FILTER_SHARED:
            h = np.asarray(arrs)
            shared = h if shared is None else shared * h
        elif mode == FILTER_FULL:
            h = np.asarray(arrs)
            full = h if full is None else full * h
        else:  # outer
            u, v = arrs
            us.append(np.asarray(u, np.float32).reshape(u.shape[0], -1))
            vs.append(np.asarray(v, np.float32).reshape(v.shape[0], -1))
    if full is not None:
        if shared is not None:
            full = full * (shared[None, :] if axis == 1 else shared[:, None])
        if us:
            u = np.concatenate(us, axis=1)
            v = np.concatenate(vs, axis=1)
            # fold the rank-K phase into the explicit filter (float32 phase,
            # matching the kernel's in-VMEM synthesis)
            phase = (u @ v.T).astype(np.float32) if axis == 1 \
                else (v @ u.T).astype(np.float32)
            full = full * np.exp(1j * phase.astype(np.float64)).astype(
                full.dtype)
        return FILTER_FULL, (full,)
    if us:
        u = np.concatenate(us, axis=1)
        v = np.concatenate(vs, axis=1)
        if shared is not None:
            return FILTER_SHARED_OUTER, (shared, u, v)
        return FILTER_OUTER, (u, v)
    return FILTER_SHARED, (shared,)


# composed per-dispatch payload cache: (cfg, plan, fuse, backend) -> payloads
# (bounded like _BUILD_CACHE — composed full filters are scene-sized too)
_PAYLOAD_CACHE: dict = {}
_PAYLOAD_CACHE_MAX = 64


# payload marker for a cross-axis (megakernel) group: the arrays slot
# holds one (axis, mode, arrays) record per in-kernel segment
MEGA = "mega"


def _group_payloads(plan: SpectralPlan, cfg, fuse,
                    backend: str) -> list:
    key = (cfg, plan, fuse, backend)
    if key not in _PAYLOAD_CACHE:
        atoms = _flatten(plan)
        groups = _group_atoms(atoms, fuse)
        payloads = []
        for g in groups:
            if g[0].kind not in ("fft", "ifft", "mul"):
                payloads.append((FILTER_NONE, ()))
                continue
            segs = _split_segments(g)
            if len(segs) == 1:
                payloads.append(
                    _compose_group_filters(g, cfg, plan.params, g[0].axis))
            else:
                payloads.append((MEGA, tuple(
                    (s[0].axis,
                     *_compose_group_filters(s, cfg, plan.params, s[0].axis))
                    for s in segs)))
        _fifo_put(_PAYLOAD_CACHE, key, (groups, payloads),
                  _PAYLOAD_CACHE_MAX)
    return _PAYLOAD_CACHE[key]


# ---------------------------------------------------------------------------
# Compiled pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Step:
    """One compiled dispatch (or one oracle op in the xla backend).

    Besides the executable ``fn``, a step carries a declarative record of
    the dispatch it performs (``kind``, ``phys_axis``, ``filter_mode``,
    ``filter_kw``, ``kernel_kw``) so a compiled pipeline can be
    *re-lowered* to another execution substrate without recompiling the
    plan — e.g. :func:`repro.core.sar.distributed.lower_pipeline` replays
    spectral steps on shard_map slabs, re-issuing ``ops.spectral_op`` per
    device with the line-indexed filter payloads sharded alongside the
    data (the multi-device analogue of ``strip_fn``'s host strips).
    """

    name: str
    fn: Callable[[jnp.ndarray], jnp.ndarray]
    dispatches: int
    hbm_roundtrips: int
    fused: bool
    stream_axis: Optional[int] = None     # data axis strips run along
    strip_fn: Optional[Callable] = None   # fn(x_strip, lo, hi)
    kind: str = "spectral"                # "spectral" | "transpose" | custom
    phys_axis: Optional[int] = None       # physical transform axis
    filter_mode: str = FILTER_NONE        # composed kernel filter mode
    filter_kw: Optional[dict] = None      # device filter payloads (line-indexed)
    kernel_kw: Optional[dict] = None      # ops.spectral_op config kwargs
    # mega steps only: per-segment scene-coordinate filter payloads,
    # aligned with kernel_kw["segments"] — one tuple of device arrays per
    # segment record, in the flat order ops.mega_spectral_op consumes.
    # This is what lets lower_sharded split the in-kernel segment chain at
    # corner-turn boundaries and re-shard each group's filters per device.
    seg_filter_args: Optional[tuple] = None
    # (physical axis, length, factors) of the transform a per-axis Pallas
    # dispatch runs; None for any other step
    transform: Optional[tuple] = None
    # Pallas spectral and mega steps: fn's two f32 result planes, before
    # unsplit joins them (the jitted pipeline interleaves them instead)
    planes: Optional[Callable] = None


@dataclasses.dataclass
class Pipeline:
    """A compiled plan: a named sequence of dispatch steps.

    Execution surfaces (all share the same compiled steps):

    * :meth:`run` — in-memory, blocking per jax's usual async dispatch.
    * :meth:`jitted` — the same step sequence traced into ONE XLA
      computation (the serving hot path; amortizes per-step dispatch).
    * :meth:`run_streamed` — strip-wise over a host-resident scene that
      exceeds device memory.
    * :meth:`lower_sharded` — re-lower to multi-device shard_map slabs
      with corner-turn collectives (transpose-free spectral plans and
      mega plans; in a mega step the in-kernel corner turns become the
      all_to_alls).

    A Pipeline holds materialized device filter payloads for one
    ``(SceneConfig, plan)`` pair; the payloads come from the bounded
    module-level caches, so building the same pipeline twice skips all
    host filter math (see :func:`filter_cache_stats`). For a process that
    serves many geometries, prefer :func:`cached_pipeline`, which also
    reuses the compiled Pipeline object itself.
    """

    name: str
    cfg: Any
    steps: list[Step]
    plan: Optional[SpectralPlan] = None

    @property
    def dispatches(self) -> int:
        return sum(s.dispatches for s in self.steps)

    @property
    def hbm_roundtrips(self) -> int:
        return sum(s.hbm_roundtrips for s in self.steps)

    def spectral_dispatches(self) -> list[tuple]:
        """``(step name, physical axis, length, factors)`` of each
        per-axis spectral dispatch that transforms, in order (a fused1
        megakernel runs its segments in one dispatch and lists none).
        Each runs under a scope inside its step's that names its length
        and split (``range_comp_rcmc/dft6144_48x128``)."""
        return [(s.name, *s.transform) for s in self.steps
                if s.transform]

    def run(self, raw: jnp.ndarray) -> jnp.ndarray:
        """Execute the compiled steps on one scene ``(na, nr)`` or a
        batch ``(B, na, nr)`` sharing the SceneConfig, complex64 in/out.

        A batched input runs each stage as a SINGLE dispatch whose grid
        spans ``B × line-blocks`` — batching is a grid extension, not a
        python loop, so the batched image equals the per-scene image
        bit-for-bit (asserted in tests/test_service.py). Steps execute
        eagerly; wrap with :meth:`jitted` to fuse the inter-step glue.
        """
        return self._run(raw)

    def _run(self, raw: jnp.ndarray, interleaved: bool = False):
        """The steps in order, each under its own scope; ``interleaved``
        returns the image as :func:`interleave` of the last step's f32
        planes (its complex result split where it has no planes)."""
        x = raw
        last = len(self.steps) - 1
        for i, s in enumerate(self.steps):
            with jax.named_scope(s.name):
                if not (interleaved and i == last):
                    x = s.fn(x)
                elif s.planes is not None:
                    x = interleave(*s.planes(x),
                                   interpret=s.kernel_kw["interpret"])
                else:
                    x = interleave(*split(s.fn(x)))
        return x

    def jitted(self) -> Callable[[jnp.ndarray], FocusedImages]:
        """One jax.jit program for the whole step sequence. Retraces per
        distinct input shape (each batch size B is one trace); the
        focusing service pre-traces its micro-batch sizes at warm-up.

        The image contract: the program takes complex64 ``(..., na, nr)``
        and returns one f32 array ``(..., na, 2·nr)``, the last step's
        two f32 planes interleaved (:func:`interleave`) with no complex64
        join. The returned callable wraps it as :class:`FocusedImages`,
        complex64 ``(..., na, nr)`` to the host: the copy back happens in
        ``np.asarray``, as a plain f32 copy viewed as complex64, and the
        host image equals :meth:`run`'s. ``.lower`` lowers the program.

        The function is named ``focus_<pipeline name>`` (HLO module
        ``jit_focus_<name>``) and each step's operations carry the step's
        name as their scope (the interleave its last step's ``unsplit``),
        so a profile attributes each device operation to a plan step; the
        compiler's own split of the complex argument where it enters
        carries the argument's name."""
        def f(raw):
            return self._run(raw, interleaved=True)
        f.__name__ = f.__qualname__ = f"focus_{self.name}"
        program = jax.jit(f)

        def focus(raw) -> FocusedImages:
            return FocusedImages(program(raw))
        focus.lower = program.lower
        return focus

    def lower_sharded(self, mesh, axes=("data",), **kw):
        """Lower this compiled pipeline onto a device mesh: every
        spectral step runs on slabs sharded along its free (line) axis,
        with an all_to_all corner turn inserted wherever consecutive
        steps transform different axes. A mega step is split at its
        in-kernel turn boundaries into per-device segment groups — one
        staged megakernel dispatch per device per group, the turns
        between groups becoming the collectives. Transpose/custom stages
        do not lower. See
        :func:`repro.core.sar.distributed.lower_pipeline` for the
        collective-bytes story; returns ``fn(raw) -> image``."""
        from repro.core.sar import distributed
        return distributed.lower_pipeline(self, mesh, axes=axes, **kw)

    def run_streamed(self, raw, strips: int = 4,
                     inflight: int = 2) -> np.ndarray:
        """Execute over host memory in `strips` tiles per stage.

        Each dispatch runs strip-by-strip along its free (line) axis with
        the line-indexed filter payloads sliced to the strip, so a scene
        that cannot fit in one device buffer still flows through the same
        compiled stages. Up to `inflight` strips are kept un-synchronized
        so jax's async dispatch overlaps the next strip's host->device
        transfer with the current strip's compute. Output is bit-identical
        to `run` (the kernel treats line blocks independently).
        """
        x = np.ascontiguousarray(np.asarray(raw))
        if x.ndim != 2:
            raise ValueError("run_streamed expects one (na, nr) scene")
        for step in self.steps:
            if step.stream_axis is None or step.strip_fn is None:
                raise ValueError(
                    f"step {step.name!r} does not support streaming "
                    "(global transposes need the whole scene; cross-axis "
                    "megakernel steps have no single free axis to strip "
                    "— use a per-axis variant like fused3)")
            ax = step.stream_axis
            n = x.shape[ax]
            sizes = [n // strips + (1 if i < n % strips else 0)
                     for i in range(strips)]
            out = np.empty(x.shape, x.dtype)
            pending: deque = deque()
            lo = 0
            for size in sizes:
                if size == 0:
                    continue
                hi = lo + size
                sl = ((slice(lo, hi), slice(None)) if ax == 0
                      else (slice(None), slice(lo, hi)))
                xs = jax.device_put(x[sl])
                pending.append((sl, step.strip_fn(xs, lo, hi)))
                while len(pending) >= max(1, inflight):
                    s2, y2 = pending.popleft()
                    out[s2] = np.asarray(y2)   # blocks; later strips overlap
                lo = hi
            while pending:
                s2, y2 = pending.popleft()
                out[s2] = np.asarray(y2)
            x = out
        return x


# ---------------------------------------------------------------------------
# The compiler
# ---------------------------------------------------------------------------

def _tuned_config(n: int, batch: int) -> KernelConfig:
    """Best-known kernel config for (n, batch) from the repro.tuning
    cache (device-fingerprinted; batch normalized to its serving bucket).
    Never triggers a sweep — compile time is lookup-only; an empty
    KernelConfig (all defaults) on a miss."""
    return cached_config(n, batch) or KernelConfig()


def _schedule_segments(opts, count: int) -> tuple:
    """Consume ``count`` per-segment configs from the compile-wide
    schedule cursor. Spectral steps take one, a mega-fused group one per
    in-kernel segment, so a Schedule's segments map onto the plan's
    spectral segments in compile order. Empty configs when compiling
    without a schedule; a schedule shorter than the plan pads with empty
    configs too (``Schedule.segment`` past-the-end behaviour)."""
    sched = opts["schedule"]
    if sched is None:
        return (SegmentConfig(),) * count
    lo = opts["_seg_cursor"][0]
    opts["_seg_cursor"][0] = lo + count
    return tuple(sched.segment(lo + i) for i in range(count))


def _schedule_globals(tuned: KernelConfig, opts) -> KernelConfig:
    """The schedule's dispatch-global knobs applied over the tuned-cache
    config. Runs BEFORE the explicit fft_kw merge, so the resolution
    order stays: explicit compile args > schedule > tuned cache >
    library defaults."""
    sched = opts["schedule"]
    if sched is None:
        return tuned
    knobs = dict(block=sched.block, col_block=sched.col_block,
                 precision=sched.precision, residency=sched.residency,
                 phase_block=sched.phase_block,
                 buffer_depth=sched.buffer_depth)
    return tuned.merge_overrides(
        {k: v for k, v in knobs.items() if v is not None})


def _payload_to_device(mode: str, arrays: tuple, axis: int,
                       transposed: bool) -> dict:
    """Scene-coordinate payload -> ops.spectral_op kwargs in the physical
    orientation (full filters transpose with the data; shared vectors and
    outer u/v are orientation-invariant given the physical axis)."""
    if mode == FILTER_NONE:
        return {}
    if mode in (FILTER_SHARED, FILTER_FULL):
        h = arrays[0]
        if mode == FILTER_FULL and transposed:
            h = np.ascontiguousarray(h.T)
        return {"hr": jnp.asarray(h.real.astype(np.float32)),
                "hi": jnp.asarray(h.imag.astype(np.float32))}
    if mode == FILTER_OUTER:
        u, v = arrays
        return {"u": jnp.asarray(u), "v": jnp.asarray(v)}
    h, u, v = arrays
    return {"hr": jnp.asarray(h.real.astype(np.float32)),
            "hi": jnp.asarray(h.imag.astype(np.float32)),
            "u": jnp.asarray(u), "v": jnp.asarray(v)}


def _slice_filter_kwargs(kw: dict, mode: str, phys_axis: int, lo: int,
                         hi: int) -> dict:
    """Slice the line-indexed filter payloads to a [lo, hi) line strip."""
    out = dict(kw)
    if mode == FILTER_FULL:
        out["hr"] = kw["hr"][lo:hi] if phys_axis == 1 else kw["hr"][:, lo:hi]
        out["hi"] = kw["hi"][lo:hi] if phys_axis == 1 else kw["hi"][:, lo:hi]
    if mode in (FILTER_OUTER, FILTER_SHARED_OUTER):
        out["u"] = kw["u"][lo:hi]
    return out


def _make_spectral_step(group, mode, arrays, *, cfg, transposed, backend,
                        opts) -> Step:
    axis = group[0].axis                       # logical (scene) axis
    phys_axis = (1 - axis) if transposed else axis
    fwd = any(a.kind == "fft" for a in group)
    inv = any(a.kind == "ifft" for a in group)
    n = cfg.nr if axis == 1 else cfg.na
    name = group[0].stage.name

    # per-dispatch kernel config: explicit compile args > stage precision >
    # schedule > tuned cache entry > library defaults
    tuned = _tuned_config(n, opts["batch"]) if (
        backend == BACKEND_PALLAS and opts["tune"] != "off") else \
        KernelConfig()
    tuned = _schedule_globals(tuned, opts)
    seg = _schedule_segments(opts, 1)[0]
    if seg.n1 is not None:
        tuned = tuned.merge_overrides(dict(n1=seg.n1, n2=seg.n2, n3=seg.n3))
    if seg.karatsuba is not None:
        tuned = tuned.merge_overrides(dict(karatsuba=seg.karatsuba))
    fkw = opts["fft_kw"] if axis == 1 else None
    if fkw:
        tuned = tuned.merge_overrides(fkw)
    if phys_axis == 1:
        block = opts["block"] or tuned.block or _line_block()
    else:
        block = opts["col_block"] or 128
    stage_prec = next((a.stage.precision for a in group
                       if a.stage.precision is not None), None)
    precision = resolve_precision(
        opts["precision"] or stage_prec or tuned.precision).name

    kernel_kw = dict(
        axis=phys_axis, fwd=fwd, inv=inv, filter_mode=mode, block=block,
        fft_impl=opts["fft_impl"], interpret=opts["interpret"],
        precision=precision, n1=tuned.n1, n2=tuned.n2,
        n3=tuned.n3, karatsuba=bool(tuned.karatsuba),
    )
    filter_kw = _payload_to_device(mode, arrays, axis, transposed)
    transform = None
    scope = f"mul{n}"
    if backend == BACKEND_PALLAS and (fwd or inv):
        transform = (phys_axis, n, _transform_factors(n, kernel_kw))
        scope = dispatch_scope(n, transform[2])

    planes = None
    if backend == BACKEND_PALLAS:
        def planes(x, _fk=filter_kw):
            xr, xi = split(x)
            with jax.named_scope(scope):
                return ops.spectral_op(xr, xi, **_fk, **kernel_kw)

        def fn(x):
            return unsplit(*planes(x))
    else:
        # the unfused oracle: same math, one jnp op per piece
        def fn(x, _fk=filter_kw):
            return _xla_apply(x, fwd, inv, mode, _fk, phys_axis)

    # streaming: strips run along the physical line axis; the scene must be
    # in its natural orientation for host strips to be meaningful
    stream_axis = None
    strip_fn = None
    if not transposed:
        stream_axis = 0 if phys_axis == 1 else 1

        def strip_fn(xs, lo, hi, _fk=filter_kw):
            fk = _slice_filter_kwargs(_fk, mode, phys_axis, lo, hi)
            if backend == BACKEND_PALLAS:
                xr, xi = split(xs)
                with jax.named_scope(scope):
                    yr, yi = ops.spectral_op(xr, xi, **fk, **kernel_kw)
                return unsplit(yr, yi)
            return _xla_apply(xs, fwd, inv, mode, fk, phys_axis)

    fused = backend == BACKEND_PALLAS and len(group) > 1
    return Step(name, fn, 1, 1, fused, stream_axis, strip_fn,
                kind="spectral", phys_axis=phys_axis, filter_mode=mode,
                filter_kw=filter_kw, kernel_kw=kernel_kw,
                transform=transform, planes=planes)


def _transform_factors(n: int, kernel_kw: dict) -> tuple:
    """The split a kernel runs for a length-n transform under
    ``kernel_kw``'s factor knobs; () for the Stockham baseline, which
    runs no DFT matmuls."""
    if kernel_kw["fft_impl"] != "matmul":
        return ()
    return resolve_factors(n, kernel_kw["n1"], kernel_kw["n2"],
                           kernel_kw["n3"])


def _seg_device_args(mode: str, arrays: tuple) -> list:
    """One segment's scene-coordinate payload as the flat device-array
    list `ops.mega_spectral_op` consumes (hr/hi pairs split re/im)."""
    if mode == FILTER_NONE:
        return []
    if mode in (FILTER_SHARED, FILTER_FULL):
        h = arrays[0]
        return [jnp.asarray(h.real.astype(np.float32)),
                jnp.asarray(h.imag.astype(np.float32))]
    if mode == FILTER_OUTER:
        u, v = arrays
        return [jnp.asarray(u), jnp.asarray(v)]
    h, u, v = arrays
    return [jnp.asarray(h.real.astype(np.float32)),
            jnp.asarray(h.imag.astype(np.float32)),
            jnp.asarray(u), jnp.asarray(v)]


def _make_mega_step(group, seg_payloads, *, cfg, backend, opts) -> Step:
    """One cross-axis fused group -> ONE megakernel dispatch (or the
    per-segment jnp oracle chain in the xla backend).

    The whole pipeline is a single `pallas_call`: per-axis segments run
    back-to-back with the corner turns inside the kernel, in the
    residency mode resolved here — explicit compile option > tuned cache
    entry > VMEM-feasibility auto-cut (repro.tuning.cost.mega_residency).

    Every precision fuses, including block-scaled bs16: the megakernel
    carries per-line block exponents through its in-kernel corner turns
    (re-blocking at each segment boundary — see fft4step.line_exponents),
    so the fused dispatch is bit-identical to the per-axis chain it
    replaces and the fused1 reroute/sharded lowering stay invisible.
    """
    segs = _split_segments(group)
    name = "+".join(dict.fromkeys(a.stage.name for a in group))

    segments = []
    filter_args: list = []
    seg_args: list = []                   # per-segment device payloads
    seg_fk: list = []                     # per-segment oracle payloads
    for atoms, (axis, mode, arrays) in zip(segs, seg_payloads):
        fwd = any(a.kind == "fft" for a in atoms)
        inv = any(a.kind == "ifft" for a in atoms)
        segments.append((axis, fwd, inv, mode))
        dev = _seg_device_args(mode, arrays)
        filter_args += dev
        seg_args.append(tuple(dev))
        fk = {}
        if mode in (FILTER_SHARED, FILTER_FULL, FILTER_SHARED_OUTER):
            fk["hr"], fk["hi"] = dev[0], dev[1]
        if mode in (FILTER_OUTER, FILTER_SHARED_OUTER):
            fk["u"], fk["v"] = dev[-2], dev[-1]
            fk["u"] = fk["u"].reshape(fk["u"].shape[0], -1)
            fk["v"] = fk["v"].reshape(fk["v"].shape[0], -1)
        seg_fk.append((axis, fwd, inv, mode, fk))
    segments = tuple(segments)

    tuned = _tuned_config(cfg.nr, opts["batch"]) if (
        backend == BACKEND_PALLAS and opts["tune"] != "off") else \
        KernelConfig()
    tuned = _schedule_globals(tuned, opts)
    seg_cfgs = _schedule_segments(opts, len(segs))
    if opts["fft_kw"]:
        tuned = tuned.merge_overrides(opts["fft_kw"])
    stage_prec = next((a.stage.precision for a in group
                       if a.stage.precision is not None), None)
    precision = resolve_precision(
        opts["precision"] or stage_prec or tuned.precision).name

    residency = opts["residency"] or tuned.residency
    if residency is None:
        from repro import tuning
        residency = tuning.cost.mega_residency(
            cfg.na, cfg.nr, precision=precision,
            filter_bytes=sum(int(a.size) * 4 for a in filter_args))
    phase_block = (opts["phase_block"] or tuned.phase_block
                   or _line_block())

    # per-segment schedule decisions ride as extended 8-field segment
    # records (axis, fwd, inv, mode, n1, n2, n3, karatsuba) — the kernel
    # resolves each against the dispatch-global factorization/karatsuba
    if any(sc != SegmentConfig() for sc in seg_cfgs):
        segments = tuple(
            rec + (sc.n1, sc.n2, sc.n3, sc.karatsuba)
            for rec, sc in zip(segments, seg_cfgs))

    kernel_kw = dict(
        segments=segments, residency=residency, phase_block=phase_block,
        fft_impl=opts["fft_impl"], interpret=opts["interpret"],
        precision=precision, n1=tuned.n1, n2=tuned.n2, n3=tuned.n3,
        karatsuba=bool(tuned.karatsuba),
    )
    if tuned.buffer_depth is not None:
        kernel_kw["buffer_depth"] = tuned.buffer_depth

    planes = None
    if backend == BACKEND_PALLAS:
        def planes(x, _fa=tuple(filter_args)):
            xr, xi = split(x)
            return ops.mega_spectral_op(xr, xi, *_fa, **kernel_kw)

        def fn(x):
            return unsplit(*planes(x))
    else:
        # the unfused oracle: the same segment chain, one jnp op per piece
        def fn(x, _sf=tuple(seg_fk)):
            for axis, fwd, inv, mode, fk in _sf:
                x = _xla_apply(x, fwd, inv, mode, fk, axis)
            return x

    fused = backend == BACKEND_PALLAS
    # stream_axis/strip_fn stay None: a cross-axis stage has no single
    # free axis to strip a host scene along, so run_streamed must reject
    # it — use a per-axis variant (fused3 & friends) there. lower_sharded
    # DOES accept this step: seg_filter_args below carries the
    # per-segment payloads it needs to split the in-kernel segment chain
    # at corner-turn boundaries into per-device groups.
    #
    # hbm_roundtrips=1 counts DISPATCH-BOUNDARY materializations of the
    # working scene (raw in, image out), the metric every step reports.
    # The staged residency additionally moves the scene through its HBM
    # scratch once per in-kernel turn — but that traffic never crosses a
    # dispatch boundary and is double-buffered behind the DFT matmuls,
    # which is precisely the difference this step exists to exploit
    # (bench rows carry residency=... so the distinction stays visible).
    return Step(name, fn, 1, 1, fused, None, None, kind="mega",
                phys_axis=None, filter_mode=MEGA, filter_kw=None,
                kernel_kw=kernel_kw, seg_filter_args=tuple(seg_args),
                planes=planes)


def _xla_apply(x, fwd, inv, mode, fk, phys_axis):
    ax = -1 if phys_axis == 1 else -2
    if fwd:
        x = jnp.fft.fft(x, axis=ax)
    if mode != FILTER_NONE:
        if mode in (FILTER_SHARED, FILTER_FULL, FILTER_SHARED_OUTER):
            h = unsplit(fk["hr"], fk["hi"])
            if mode == FILTER_SHARED or (mode == FILTER_SHARED_OUTER
                                         and h.ndim == 1):
                h = h[None, :] if phys_axis == 1 else h[:, None]
            x = x * h
        if mode in (FILTER_OUTER, FILTER_SHARED_OUTER):
            phase = jnp.einsum("lk,sk->ls", fk["u"], fk["v"])
            if phys_axis == 0:
                phase = phase.T
            x = x * jnp.exp(1j * phase.astype(jnp.complex64))
    if inv:
        x = jnp.fft.ifft(x, axis=ax)
    return x


def _make_transpose_step(stage: Stage, backend: str, interpret) -> Step:
    if backend == BACKEND_PALLAS:
        def fn(x):
            return unsplit(tiled_transpose(jnp.real(x).astype(jnp.float32),
                                           interpret=interpret),
                           tiled_transpose(jnp.imag(x).astype(jnp.float32),
                                           interpret=interpret))
    else:
        def fn(x):
            return jnp.swapaxes(x, -1, -2)
    return Step(stage.name, fn, 1, 1, False, None, None, kind="transpose")


def _make_custom_step(stage: Stage, cfg) -> Step:
    if stage.kind not in _STAGE_IMPLS:
        raise KeyError(f"no implementation registered for stage kind "
                       f"{stage.kind!r}")
    impl, stream_axis = _STAGE_IMPLS[stage.kind]
    opts = stage.opt_dict()

    def fn(x):
        return impl(x, cfg, opts, None, None)

    strip_fn = None
    if stream_axis is not None:
        def strip_fn(xs, lo, hi):
            return impl(xs, cfg, opts, lo, hi)
    return Step(stage.name, fn, 1, 1, False, stream_axis, strip_fn,
                kind=stage.kind)


def compile_plan(
    plan: SpectralPlan,
    cfg,
    *,
    backend: str = BACKEND_PALLAS,
    fuse=True,
    batch: int = 1,
    interpret: Optional[bool] = None,
    block: Optional[int] = None,
    col_block: Optional[int] = None,
    fft_impl: str = "matmul",
    precision: Optional[str] = None,
    tune: str = "cached",
    fft_kw: Optional[dict] = None,
    residency: Optional[str] = None,
    phase_block: Optional[int] = None,
    schedule: Optional[Schedule] = None,
) -> Pipeline:
    """Compile a plan against a concrete scene into a :class:`Pipeline`.

    cfg is a :class:`~repro.core.sar.SceneConfig`; the compiled pipeline
    accepts one ``(cfg.na, cfg.nr)`` complex64 scene or any batch
    ``(B, na, nr)`` of scenes sharing that geometry (B is a runtime shape,
    not a compile parameter — see ``batch`` below).

    backend: 'pallas' (fused dispatches) or 'xla' (jnp oracle ops).
    fuse: merge adjacent compatible atoms into single dispatches. ``True``
      fuses per transform axis; :data:`FUSE_MEGA` ("mega") additionally
      fuses ACROSS axis changes into single-dispatch megakernel steps
      (in-kernel corner turns — the fused1 pipeline family).
    residency: megakernel execution mode for mega-fused steps — 'vmem'
      (whole slab on-chip) or 'staged' (HBM scratch + double-buffered
      DMA); None auto-selects by the repro.tuning VMEM-feasibility cut.
    phase_block: lines per staged-phase grid step (None = tuned, else
      the device's line block, repro.tuning.cost.DeviceSpec.line_block).
    batch: scene-batch size the tuned configs are *looked up* for
      (normalized to the serving power-of-two bucket by repro.tuning);
      it does not restrict the shapes the pipeline accepts.
    block/col_block: line blocks for rows/columns dispatches (None = the
      autotuned or library default).
    precision: global matmul-operand policy override for every spectral
      stage (see fft4step.PRECISIONS); per-stage ``Stage.precision`` wins
      over the autotune cache but not over this.
    tune: 'cached' pulls per-dispatch kernel configs from the
      repro.tuning cache; 'off' uses library defaults.
    fft_kw: explicit config for range-axis (axis=1) dispatches — e.g. a
      just-measured factorization from a repro.tuning search.
    schedule: a :class:`repro.tuning.Schedule` (the schedule-graph search
      winner) to compile through. Its dispatch-global knobs override the
      tuned-cache entry and its per-segment factorization/karatsuba
      decisions map onto the plan's spectral segments in compile order —
      a mega-fused group consumes one per in-kernel segment, reaching
      the kernel as extended segment records; other spectral steps one
      each. Explicit per-knob compile args (block, precision, fft_kw,
      residency, ...) still win over the schedule.

    Cache behaviour: composed filter payloads are served from the bounded
    ``(cfg, plan, fuse, backend)`` payload cache and the underlying host
    filter math from the ``(cfg, params, name)`` build cache, so
    recompiling the same (scene, plan) pair does no host filter work.
    The Pipeline object itself is rebuilt each call — use
    :func:`cached_pipeline` to also reuse compiled pipelines (and their
    jit traces) across calls, e.g. from the focusing service.
    """
    if backend not in (BACKEND_PALLAS, BACKEND_XLA):
        raise ValueError(f"unknown backend {backend!r}")
    groups, payloads = _group_payloads(plan, cfg, fuse, backend)
    opts = dict(batch=batch, tune=tune, fft_kw=fft_kw or {}, block=block,
                col_block=col_block, fft_impl=fft_impl,
                interpret=interpret, precision=precision,
                residency=residency, phase_block=phase_block,
                schedule=schedule, _seg_cursor=[0])
    steps: list[Step] = []
    transposed = False
    for group, (mode, arrays) in zip(groups, payloads):
        kind = group[0].kind
        if mode == MEGA:
            if transposed:
                raise ValueError(
                    f"mega step {group[0].stage.name!r} inside a "
                    "transposed section is not supported")
            steps.append(_make_mega_step(
                group, arrays, cfg=cfg, backend=backend, opts=opts))
        elif kind in ("fft", "ifft", "mul"):
            steps.append(_make_spectral_step(
                group, mode, arrays, cfg=cfg, transposed=transposed,
                backend=backend, opts=opts))
        elif kind == "transpose":
            steps.append(_make_transpose_step(group[0].stage, backend,
                                              interpret))
            transposed = not transposed
        else:
            if transposed:
                raise ValueError(
                    f"custom stage {group[0].stage.name!r} inside a "
                    "transposed section is not supported")
            steps.append(_make_custom_step(group[0].stage, cfg))
    if transposed:
        raise ValueError(f"plan {plan.name!r} ends in transposed orientation")
    return Pipeline(plan.name, cfg, steps, plan)


# ---------------------------------------------------------------------------
# Variant registry — named plans + their compile defaults
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Variant:
    """A registered pipeline variant: a plan factory, how to compile it,
    and its documented dispatch count (the fusion-legality invariant)."""

    name: str
    plan_fn: Callable[..., SpectralPlan]
    compile_defaults: tuple[tuple[str, Any], ...] = ()
    plan_kw: tuple[str, ...] = ()       # build kwargs routed to plan_fn
    dispatches: int = 0                 # documented compiled dispatch count


_VARIANTS: dict[str, Variant] = {}


def register_variant(name: str, plan_fn, *, compile_defaults=(),
                     plan_kw=(), dispatches=0) -> None:
    _VARIANTS[name] = Variant(name, plan_fn, tuple(compile_defaults),
                              tuple(plan_kw), dispatches)


def get_variant(name: str) -> Variant:
    if name not in _VARIANTS:
        raise KeyError(f"unknown pipeline variant {name!r}; "
                       f"registered: {sorted(_VARIANTS)}")
    return _VARIANTS[name]


def variant_names() -> tuple[str, ...]:
    return tuple(sorted(_VARIANTS))


def build_variant(cfg, name: str, **kw) -> Pipeline:
    """Build + compile a registered variant. Plan-level kwargs (declared in
    the variant's plan_kw) route to the plan factory; the rest override the
    variant's compile defaults and go to compile_plan."""
    var = get_variant(name)
    plan_args = {k: kw.pop(k) for k in list(kw) if k in var.plan_kw}
    compile_args = dict(var.compile_defaults)
    compile_args.update(kw)
    return compile_plan(var.plan_fn(**plan_args), cfg, **compile_args)


# ---------------------------------------------------------------------------
# Compiled-pipeline cache — the serving hot path
# ---------------------------------------------------------------------------
#
# compile_plan is cheap-ish (payloads are cached) but not free, and a fresh
# Pipeline means fresh jit traces. A server coalescing requests into
# micro-batches wants ONE warm Pipeline per (scene geometry, variant,
# compile options) so every request after the first reuses both the
# compiled steps and their XLA executables. Bounded FIFO like the filter
# caches: pipelines hold scene-sized device filter payloads.

_PIPELINE_CACHE: dict = {}
_PIPELINE_CACHE_MAX = 32


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    return v


def cached_pipeline(cfg, variant: str, **kw) -> Pipeline:
    """`build_variant` behind a bounded cache keyed on
    ``(cfg, variant, compile kwargs)``. Repeated calls return the SAME
    Pipeline object, so jit traces, device filter payloads, and autotune
    lookups are all warm. Unhashable kwarg values (dicts/lists, e.g.
    ``fft_kw``) are frozen to tuples for the key."""
    key = (cfg, variant, _freeze(kw))
    if key not in _PIPELINE_CACHE:
        import repro.core.sar  # noqa: F401  (registers the shipped variants)
        _fifo_put(_PIPELINE_CACHE, key, build_variant(cfg, variant, **kw),
                  _PIPELINE_CACHE_MAX)
    return _PIPELINE_CACHE[key]


def clear_pipeline_cache() -> None:
    _PIPELINE_CACHE.clear()
