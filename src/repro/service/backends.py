"""Pluggable execution backends for the focusing service.

A backend turns one coalesced micro-batch into focused images, blocking
the calling thread (the service invokes it through an executor so the
event loop keeps admitting requests while the device computes). Two are
shipped:

``local``    One-device execution through the warm compiled-pipeline
             cache (`core.plan.cached_pipeline`): per BatchKey, ONE
             Pipeline whose jit traces, filter payloads, and tuned
             configs persist across requests. Scenes whose whole slab
             fits the VMEM budget are transparently routed from their
             per-axis variant to its single-dispatch megakernel twin
             (FUSED1_TWINS; bit-identical at every precision,
             `fused1="off"` opts out).
             `warm()` optionally sweeps
             a few (block, col_block) line-block configs on the real
             batched pipeline and pins the winner — interpret-mode CPU
             timing is too shape-dependent for the kernel-level cache
             alone (same rationale as benchmarks/bench_rda.run_batched).
             The sweep runs through `repro.tuning.measured_search` and
             its winner persists to the shared device-fingerprinted
             tuning cache under a pipeline-kind TuneKey, so serving
             warms survive process restarts: the next process's `warm()`
             is a cache hit and pays only the jit traces. Big streamed
             scenes route to the SHARDED megakernel twin when multiple
             devices are visible and the cost model prefers it
             (`sharded="off"` opts out; see `execute_streamed`).

``sharded``  Multi-device execution via the shard_map corner-turn
             lowering (`core.sar.distributed.build_sharded`): schedule
             'corner2' lowers the compiled plan generically (all_to_all
             at each transform-axis change), 'halo' uses the hand-written
             single-turn RDA schedule. Oversized scenes route through the
             mesh too — P devices hold P× the budget — so this backend
             has no separate streaming path.
"""
from __future__ import annotations

import logging
import time
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.kernels.fft4step import resolve_precision
from repro.service.queue import BatchKey
from repro.service.resilience import BreakerBoard
from repro import tuning

_log = logging.getLogger(__name__)


def _resolve_blocks(cfg, block: Optional[int], col_block: Optional[int]):
    """-1 means 'all lines' for the respective dispatch orientation."""
    if block == -1:
        block = cfg.na
    if col_block == -1:
        col_block = cfg.nr
    return block, col_block


# Batch-size buckets are powers of two: every distinct batch shape costs
# one jit trace (hundreds of ms), so a partial batch pads with zero
# scenes up to the next pre-traced bucket instead of compiling a fresh
# executable mid-serving. Zero scenes are numerically inert (every stage
# maps 0 -> 0) and their rows are sliced off the reply. The SAME buckets
# key the tuning cache (tuning.TuneKey normalizes batch through this), so
# a padded batch always looks up the config tuned for the shape that
# actually runs.
_bucket = tuning.bucket_batch

# Per-axis variants with a single-dispatch megakernel twin: when the
# scene's whole slab fits the VMEM budget (repro.tuning.cost.mega_residency
# says 'vmem'), the local backend transparently serves these through the
# fused1 pipeline — same math bit-for-bit at EVERY precision (asserted in
# tests: bs16 carries per-line block exponents through the in-kernel
# corner turns, so the fused dispatch quantizes exactly like the per-axis
# chain), one dispatch and zero HBM intermediates instead of three
# round-trips.
FUSED1_TWINS = {
    "fused3": "fused1",
    "csa_fused": "csa_fused1",
    "omegak": "omegak_fused1",
}

# Last-resort degradation tier: the DEFUSED chain for a fused per-axis
# variant — more, smaller dispatches through the same plan stages. Unlike
# the fused1 twin this step is NOT bit-identical (stage-boundary rounding
# differs), so it only serves after both fused tiers have failed: a
# numerically equivalent image beats a failed request. omega-K has no
# defused sibling (its Stolt interpolation only exists fused), so its
# chain ends at the per-axis tier.
DEFUSED_FALLBACK = {
    "fused3": "unfused",
    "fused": "unfused",
    "csa_fused": "csa",
}


def _pad_batch(batch: np.ndarray) -> np.ndarray:
    b = batch.shape[0]
    pb = _bucket(b)
    if pb == b:
        return batch
    pad = np.zeros((pb - b, *batch.shape[1:]), batch.dtype)
    return np.concatenate([batch, pad])


class LocalBackend:
    """Single-device backend over the compiled-pipeline cache."""

    name = "local"

    def __init__(self, sweep: Sequence[Tuple[Optional[int], Optional[int]]]
                 = ((None, None), (32, -1)), tune_cache=None,
                 fused1: str = "auto", sharded: str = "auto",
                 fallback: str = "auto",
                 breakers: Optional[BreakerBoard] = None):
        if fused1 not in ("auto", "off"):
            raise ValueError(f"fused1 must be 'auto' or 'off', got "
                             f"{fused1!r}")
        if sharded not in ("auto", "off"):
            raise ValueError(f"sharded must be 'auto' or 'off', got "
                             f"{sharded!r}")
        if fallback not in ("auto", "off"):
            raise ValueError(f"fallback must be 'auto' or 'off', got "
                             f"{fallback!r}")
        self.sweep = tuple(sweep)
        self.fused1 = fused1
        self.sharded = sharded
        self.fallback = fallback            # "off" disables degraded tiers
        # per-route circuit breakers (route x variant x shape x precision):
        # a route that keeps failing is skipped on the hot path until its
        # cooldown expires, then re-probed half-open
        self.breakers = breakers if breakers is not None else BreakerBoard()
        self.fallbacks: Counter = Counter()  # degraded-route serve counts
        self._tune_cache = tune_cache       # None -> the shared default
        self._best: Dict[BatchKey, Tuple[Optional[int], Optional[int]]] = {}
        self._sched: Dict[BatchKey, "tuning.Schedule"] = {}
        self._fns: Dict[Tuple[BatchKey, str], callable] = {}
        self._sharded_fns: Dict[BatchKey, callable] = {}

    def _route_variant(self, key: BatchKey) -> str:
        """The variant actually compiled for a BatchKey: VMEM-fitting
        scenes requesting a per-axis variant with a megakernel twin are
        served by the single-dispatch fused1 pipeline (`fused1="off"`
        pins the requested variant). The route must be invisible — the
        served image equals the requested variant's bit-for-bit — and it
        is, at every precision: f32/bf16/f16 trivially (the fused kernel
        runs the identical per-segment math), and bs16 because the
        megakernel carries per-line block exponents through its in-kernel
        corner turns, quantizing exactly as the three dispatches would
        (the route-invisibility matrix in tests/test_service.py)."""
        twin = FUSED1_TWINS.get(key.variant)
        if (self.fused1 == "auto" and twin is not None
                and tuning.cost.mega_residency(key.scene.na, key.scene.nr)
                == "vmem"):
            return twin
        return key.variant

    def _pipeline(self, key: BatchKey, batch: int = 1,
                  variant: Optional[str] = None):
        """The compiled pipeline serving ``key`` — at the routed tier-0
        variant by default, or at an explicit ``variant`` (a degraded
        tier, or the requested per-axis variant for sweeps/streams)."""
        block, col_block = _resolve_blocks(
            key.scene, *self._best.get(key, (None, None)))
        kw = dict(batch=batch)
        if key.precision is not None:
            kw["precision"] = key.precision
        if block is not None:
            kw["block"] = block
        if col_block is not None:
            kw["col_block"] = col_block
        sched = self._sched.get(key)
        if sched is not None:
            kw["schedule"] = sched
        if variant is None:
            variant = self._route_variant(key)
        return planlib.cached_pipeline(key.scene, variant, **kw)

    def _fn(self, key: BatchKey, variant: Optional[str] = None):
        if variant is None:
            variant = self._route_variant(key)
        if (key, variant) not in self._fns:
            self._fns[(key, variant)] = \
                self._pipeline(key, variant=variant).jitted()
        return self._fns[(key, variant)]

    # -- tiered degradation --------------------------------------------------
    def _execute_tiers(self, key: BatchKey) -> List[Tuple[str, str]]:
        """Ordered (route_name, variant) tiers for a coalesced batch:
        the megakernel twin (when routed), the requested per-axis
        variant, and — unless ``fallback="off"`` — the defused chain.
        Tier 0 is EXACTLY what `_route_variant` serves on the fault-free
        path, so degradation never changes healthy results."""
        routed = self._route_variant(key)
        tiers = [("fused1" if routed != key.variant else "plan", routed)]
        if routed != key.variant:
            tiers.append(("plan", key.variant))
        if self.fallback == "auto":
            defused = DEFUSED_FALLBACK.get(key.variant)
            if defused is not None and defused != key.variant:
                tiers.append(("defused", defused))
        return tiers

    def _breaker(self, route: str, variant: str, key: BatchKey):
        cfg = key.scene
        return self.breakers.get(
            f"{route}:{variant}:{cfg.na}x{cfg.nr}:{key.precision}")

    def _tune_key(self, key: BatchKey, max_batch: int) -> "tuning.TuneKey":
        cfg = key.scene
        return tuning.TuneKey.pipeline(
            variant=key.variant, na=cfg.na, nr=cfg.nr, batch=max_batch,
            precision=key.precision)

    def warm(self, key: BatchKey, max_batch: int = 4) -> None:
        """Pre-pull everything a request would otherwise pay for: compile
        the plan (materializing filters + tuned kernel configs), resolve
        the (block, col_block) pipeline config — from the shared tuning
        cache when a previous process already swept this key, else by
        running the sweep through `repro.tuning.measured_search` on a
        B=max_batch scene batch and persisting the winner — and pre-trace
        the jit executable for every power-of-two batch bucket up to
        max_batch (partial batches pad to a bucket at execute time)."""
        cfg = key.scene
        zeros = jnp.zeros((_bucket(max_batch), cfg.na, cfg.nr),
                          jnp.complex64)
        if len(self.sweep) > 1 and key not in self._best:
            tune_cache = self._tune_cache or tuning.get_cache()
            tkey = self._tune_key(key, max_batch)
            try:
                hit = tune_cache.get(tkey)
                sched = tune_cache.get_schedule(tkey)
            except Exception:
                hit = sched = None
                              # corrupt/foreign-schema file: fall back to
                              # the in-process sweep, never fail warm-up
            if hit is not None:
                self._best[key] = (hit.block, hit.col_block)
                # a persisted graph-search Schedule carries per-segment
                # decisions the flat config can't express — compile the
                # served pipeline through it; a degenerate (flat-derived)
                # schedule adds nothing, so skip it and keep the cache
                # key identical to the pre-schedule one
                if sched is not None and \
                        sched != tuning.Schedule.from_config(hit):
                    self._sched[key] = sched
            else:
                def measure(cand, iters):
                    blk, cb = cand
                    self._best[key] = (blk, cb)
                    # sweep the REQUESTED per-axis pipeline: a mega-routed
                    # pipeline ignores (block, col_block), so timing it
                    # would persist a noise winner to the cache — the swept
                    # config is what execute_streamed and fused1="off"
                    # processes actually consume
                    f = self._pipeline(key, batch=max_batch,
                                       variant=key.variant).jitted()
                    jax.block_until_ready(f(zeros))   # compile
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(zeros))
                    return time.perf_counter() - t0

                best, seconds, _ = tuning.measured_search(
                    self.sweep, measure, rungs=(1,))
                self._best[key] = best
                try:
                    tune_cache.put(
                        tkey,
                        tuning.KernelConfig(block=best[0],
                                            col_block=best[1]),
                        seconds=seconds, source="sweep")
                except Exception:
                    pass      # read-only cache dir: the sweep result still
                              # serves this process, it just won't persist
        f = self._fn(key)
        b = 1
        while b <= zeros.shape[0]:
            jax.block_until_ready(f(zeros[:b]))
            b *= 2

    def execute(self, key: BatchKey, batch: np.ndarray) -> np.ndarray:
        """(B, na, nr) host batch -> (B, na, nr) focused images.
        Pads to the nearest power-of-two bucket (see `_bucket`).

        Walks the degradation tiers (`_execute_tiers`): a tier whose
        circuit breaker is open is skipped (until its cooldown admits a
        half-open probe), a tier that raises records the failure and
        falls through to the next, and the LAST tier always runs so a
        request is never failed by an open breaker alone. On the
        fault-free path tier 0 serves and the result is bit-identical to
        the pre-resilience backend."""
        b = batch.shape[0]
        padded = jnp.asarray(_pad_batch(batch))
        tiers = self._execute_tiers(key)
        last_err: Optional[Exception] = None
        for i, (route, variant) in enumerate(tiers):
            br = self._breaker(route, variant, key)
            if i < len(tiers) - 1 and not br.allow():
                self.fallbacks[f"skip:{route}"] += 1
                continue
            try:
                out = np.asarray(self._fn(key, variant)(padded))
            except Exception as e:          # noqa: BLE001 — tier boundary
                br.record_failure()
                last_err = e
                _log.warning("route %s:%s failed for %dx%d: %s: %s", route,
                             variant, key.scene.na, key.scene.nr,
                             type(e).__name__, str(e).split("\n")[0][:300])
                continue
            br.record_success()
            if (route, variant) != tiers[0]:
                self.fallbacks[f"serve:{route}"] += 1
            return out[:b]
        raise last_err

    def _sharded_twin(self, key: BatchKey) -> Optional[str]:
        """The megakernel twin to run SHARDED for a big streamed scene,
        or None to keep the host-strip path. Routes when a twin exists
        (any precision — bs16's carried exponents all_gather across the
        corner turns, so the sharded image stays bit-identical), the
        scene tiles the mesh, and the roofline prefers P per-device
        megakernels plus collective corner turns over strip-streaming
        one device (`repro.tuning.cost.sharded_preferred`)."""
        twin = FUSED1_TWINS.get(key.variant)
        p = len(jax.devices())
        if (self.sharded != "auto" or self.fused1 == "off" or twin is None
                or p <= 1):
            return None
        cfg = key.scene
        prec = resolve_precision(key.precision).name
        if not tuning.cost.sharded_preferred(cfg.na, cfg.nr, devices=p,
                                             precision=prec):
            return None
        return twin

    def _sharded_fn(self, key: BatchKey):
        if key not in self._sharded_fns:
            from repro.core.sar.distributed import make_sar_mesh
            kw = {}
            if key.precision is not None:
                kw["precision"] = key.precision
            pipe = planlib.cached_pipeline(
                key.scene, self._sharded_twin(key), **kw)
            self._sharded_fns[key] = pipe.lower_sharded(make_sar_mesh())
        return self._sharded_fns[key]

    def execute_streamed(self, key: BatchKey, raw: np.ndarray,
                         strips: int = 4) -> np.ndarray:
        """One host-resident scene, over the single-device budget.

        Default path: Pipeline.run_streamed on the REQUESTED per-axis
        variant (strip transfer overlapped with compute; bit-identical
        to `execute`) — the streaming executor strips one free axis at a
        time, which a cross-axis megakernel step deliberately refuses.

        Multi-device path: when the cost model prefers it
        (`_sharded_twin`), the scene runs as the variant's megakernel
        twin lowered through shard_map — one staged megakernel dispatch
        per device per phase group, all_to_all corner turns between
        groups, each device holding a 1/P slab. Every precision is
        bit-identical to the per-axis strip path (asserted in tests;
        bs16's carried exponents ride the collectives), so the route
        stays invisible.

        Degradation: a failing (or breaker-open) sharded route falls
        back to the single-device strip path — sharded -> local is
        bit-identical, so the fallback is invisible beyond latency."""
        if self._sharded_twin(key) is not None:
            br = self._breaker("sharded", self._sharded_twin(key), key)
            if br.allow():
                try:
                    out = np.asarray(self._sharded_fn(key)(jnp.asarray(raw)))
                except Exception as e:      # noqa: BLE001 — tier boundary
                    br.record_failure()
                    _log.warning("sharded route failed, streaming locally: "
                                 "%s: %s", type(e).__name__,
                                 str(e).split("\n")[0][:300])
                    self.fallbacks["serve:local_stream"] += 1
                else:
                    br.record_success()
                    return out
            else:
                self.fallbacks["skip:sharded"] += 1
        return np.asarray(self._pipeline(key, variant=key.variant)
                          .run_streamed(raw, strips=strips))


class ShardedBackend:
    """Multi-device backend over the shard_map corner-turn lowering."""

    name = "sharded"

    def __init__(self, mesh=None, axes=("data",), schedule: str = "corner2",
                 turn_dtype=None):
        if mesh is None:
            # multi-host capable: contiguous per-host device blocks
            # (corner2 layout) — see distributed.make_sar_mesh
            from repro.core.sar.distributed import make_sar_mesh
            mesh = make_sar_mesh(axes)
        self.mesh = mesh
        self.axes = axes
        self.schedule = schedule
        self.turn_dtype = turn_dtype
        self._fns: Dict[BatchKey, callable] = {}

    def _fn(self, key: BatchKey):
        if key not in self._fns:
            from repro.core.sar.distributed import build_sharded
            kw = {}
            if key.precision is not None:
                kw["precision"] = key.precision
            self._fns[key] = build_sharded(
                key.scene, key.variant, self.mesh, self.axes,
                schedule=self.schedule, turn_dtype=self.turn_dtype, **kw)
        return self._fns[key]

    def warm(self, key: BatchKey, max_batch: int = 4) -> None:
        cfg = key.scene
        fn = self._fn(key)
        if self.schedule == "halo":        # 2-D runner: one trace
            jax.block_until_ready(fn(jnp.zeros((cfg.na, cfg.nr),
                                               jnp.complex64)))
            return
        zeros = jnp.zeros((_bucket(max_batch), cfg.na, cfg.nr),
                          jnp.complex64)
        b = 1
        while b <= zeros.shape[0]:
            jax.block_until_ready(fn(zeros[:b]))
            b *= 2

    def execute(self, key: BatchKey, batch: np.ndarray) -> np.ndarray:
        fn = self._fn(key)
        if self.schedule == "halo":        # the halo runner is per-scene
            return np.stack([np.asarray(fn(jnp.asarray(x))) for x in batch])
        b = batch.shape[0]
        return np.asarray(fn(jnp.asarray(_pad_batch(batch))))[:b]

    def execute_streamed(self, key: BatchKey, raw: np.ndarray,
                         strips: int = 4) -> np.ndarray:
        # a scene over the single-device budget fits the mesh: the slabs
        # are 1/P of the scene each, so just run it sharded.
        return np.asarray(self._fn(key)(jnp.asarray(raw)))


BACKENDS = {"local": LocalBackend, "sharded": ShardedBackend}


def make_backend(name: str, **kw):
    if name not in BACKENDS:
        raise KeyError(f"unknown backend {name!r}; known: {sorted(BACKENDS)}")
    return BACKENDS[name](**kw)
