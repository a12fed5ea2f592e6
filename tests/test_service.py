"""Focusing service: coalescing bit-identity, deadline flush,
backpressure, the precision SNR gate, the streaming route, metrics
artifacts, and sharded-backend parity."""
import asyncio
import functools
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from benchmarks.common import validate_bench_doc, validate_bench_file, \
    write_bench_json
from repro.core.sar import build_pipeline, paper_targets, simulate_cached
from repro.core.sar.geometry import test_scene as make_test_scene
from repro.service import (
    BatchKey,
    FocusRequest,
    FocusService,
    LocalBackend,
    MicroBatcher,
    RequestCancelled,
    RequestQueue,
    ServiceConfig,
    ServiceOverloaded,
    ShardedBackend,
    SnrGateViolation,
    WorkerPool,
)
from repro.service.queue import now as svc_now

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
CFG = make_test_scene(128)
TARGETS = paper_targets(CFG)

def fast_backend():
    # single-config backend: tests don't need the warm-time block sweep
    return LocalBackend(sweep=((None, None),))


def scene():
    return simulate_cached(CFG, TARGETS)


def reference(variant="fused3", **kw):
    return np.asarray(build_pipeline(CFG, variant, **kw).run(
        jnp.asarray(scene())))


# ---------------------------------------------------------------------------
# Coalescing semantics
# ---------------------------------------------------------------------------

def test_coalesced_batch_bit_identical_to_per_request_run():
    """Four requests coalesced into ONE (4, na, nr) dispatch sequence must
    reproduce per-request Pipeline.run bit-for-bit — batching is a kernel
    grid extension, not a numerical rewrite."""
    raw = scene()
    ref = reference()
    ref_half = np.asarray(build_pipeline(CFG, "fused3").run(
        jnp.asarray(raw) * 0.5))

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=4, max_delay_ms=500.0, precision=None),
            backend=fast_backend())
        await svc.start()
        outs = await asyncio.gather(
            svc.focus(raw, CFG), svc.focus(raw * 0.5, CFG),
            svc.focus(raw, CFG), svc.focus(raw, CFG))
        await svc.stop()
        return outs, svc.metrics.snapshot()

    outs, snap = asyncio.run(main())
    assert snap["batch_size_hist"] == {4: 1}, snap  # actually coalesced
    assert np.array_equal(outs[0], ref)
    assert np.array_equal(outs[1], ref_half)
    assert np.array_equal(outs[2], ref)
    assert np.array_equal(outs[3], ref)


def test_partial_batch_pads_to_bucket_bit_identical():
    """A 3-request batch pads to the B=4 bucket; the zero pad scene must
    not perturb the real scenes' images."""
    raw = scene()
    ref = reference()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=3, max_delay_ms=500.0, precision=None),
            backend=fast_backend())
        await svc.start()
        outs = await asyncio.gather(*[svc.focus(raw, CFG) for _ in range(3)])
        await svc.stop()
        return outs, svc.metrics.snapshot()

    outs, snap = asyncio.run(main())
    assert snap["batch_size_hist"] == {3: 1}
    for o in outs:
        assert np.array_equal(o, ref)


def test_deadline_flush_fires_for_partial_batch():
    """Two requests under max_batch=8 must not wait forever: the
    max_delay deadline flushes the partial bucket."""
    raw = scene()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=8, max_delay_ms=50.0, precision=None),
            backend=fast_backend())
        await svc.start()
        t0 = time.monotonic()
        outs = await asyncio.gather(svc.focus(raw, CFG),
                                    svc.focus(raw, CFG))
        elapsed = time.monotonic() - t0
        await svc.stop()
        return outs, elapsed, svc.metrics.snapshot()

    outs, elapsed, snap = asyncio.run(main())
    assert snap["batch_size_hist"] == {2: 1}, snap
    assert len(outs) == 2
    # generous bound: 50 ms deadline + one small-scene batch + slack
    assert elapsed < 30.0


def test_requests_with_different_keys_do_not_coalesce():
    raw = scene()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=4, max_delay_ms=50.0, precision=None),
            backend=fast_backend())
        await svc.start()
        a, b = await asyncio.gather(
            svc.focus(raw, CFG, variant="fused3"),
            svc.focus(raw, CFG, variant="omegak"))
        await svc.stop()
        return a, b, svc.metrics.snapshot()

    a, b, snap = asyncio.run(main())
    assert snap["batch_size_hist"] == {1: 2}, snap
    assert np.array_equal(a, reference("fused3"))
    assert np.array_equal(b, reference("omegak"))


# ---------------------------------------------------------------------------
# Backpressure + SNR gate
# ---------------------------------------------------------------------------

class _GatedBackend:
    """Backend that blocks until released — lets tests hold a batch in
    flight while the queue fills behind it."""

    def __init__(self):
        self.release = threading.Event()

    def warm(self, key, max_batch=4):
        pass

    def execute(self, key, batch):
        assert self.release.wait(30)
        return np.zeros_like(batch)

    def execute_streamed(self, key, raw, strips=4):
        assert self.release.wait(30)
        return np.zeros_like(raw)


def test_backpressure_rejects_past_queue_bound():
    """The admission bound covers the TOTAL pre-dispatch backlog: queued
    requests plus the batcher's bucketed/awaiting-slot requests. With one
    lane of one slot: t1 holds the slot in flight (not backlog), t2's
    flush parks awaiting the slot (backlog 1), t3 sits in the queue
    (backlog 2 = bound) — the fourth submit is rejected. None of the
    waiters carry deadlines, so shedding (deadline-aware) cannot admit
    the arrival and the caller sees ServiceOverloaded."""
    raw = scene()
    backend = _GatedBackend()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=1, max_queue=2, precision=None,
                          lanes=1, inflight_cap=1,
                          sentinel=False),   # stub returns zero images
            backend=backend)
        await svc.start()
        t1 = asyncio.ensure_future(svc.focus(raw, CFG))
        await asyncio.sleep(0.1)        # batch 1 now executing (blocked)
        t2 = asyncio.ensure_future(svc.focus(raw, CFG))
        t3 = asyncio.ensure_future(svc.focus(raw, CFG))
        await asyncio.sleep(0.1)        # backlog now at bound (2)
        with pytest.raises(ServiceOverloaded) as exc_info:
            await svc.focus(raw, CFG)
        backend.release.set()
        outs = await asyncio.gather(t1, t2, t3)
        await svc.stop()
        return outs, exc_info.value, svc.metrics.snapshot()

    outs, err, snap = asyncio.run(main())
    assert len(outs) == 3
    assert err.depth == 2 and err.bound == 2
    assert snap["rejected"] == 1
    assert snap["completed"] == 3


def test_service_overloaded_carries_depth_bound_and_retry_hint():
    """ServiceOverloaded is machine-readable: depth, bound, and a
    retry_after_hint priced by the service-time EWMA all ride on the
    exception (and render into its message)."""

    async def main():
        q = RequestQueue(2)
        loop = asyncio.get_running_loop()

        def mk():
            return FocusRequest(
                raw=np.zeros((2, 2), np.complex64), scene=CFG,
                variant="fused3", precision=None,
                future=loop.create_future(), t_submit=svc_now())

        q.put(mk())
        q.put(mk())
        with pytest.raises(ServiceOverloaded) as ei:
            q.put(mk())
        err = ei.value
        assert err.depth == 2 and err.bound == 2
        assert err.retry_after_hint == pytest.approx(q.retry_after_hint(2))
        assert err.retry_after_hint > 0
        msg = str(err)
        assert "depth 2 >= bound 2" in msg
        assert f"retry_after_hint={err.retry_after_hint:.3f}s" in msg

        # `extra` backlog (the batcher's buckets) counts toward the bound
        with pytest.raises(ServiceOverloaded) as e2:
            q.put(mk(), extra=5)
        assert e2.value.depth == 7

        # the hint tracks observed service time: slower batches -> a
        # longer suggested backoff
        h0 = q.retry_after_hint(2)
        q.note_service_time(1.0)
        assert q.retry_after_hint(2) > h0

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Continuous batching, deadlines, worker pool
# ---------------------------------------------------------------------------

class _RecordingBackend:
    """Delegating backend that records the concurrency of execute calls
    (for overlap / in-flight-cap assertions) while computing real images."""

    def __init__(self, inner, delay: float = 0.0):
        self.inner = inner
        self.delay = delay
        self._lock = threading.Lock()
        self._active = 0
        self.max_active = 0
        self.batch_sizes = []

    def warm(self, key, max_batch=4):
        self.inner.warm(key, max_batch)

    def _enter(self):
        with self._lock:
            self._active += 1
            self.max_active = max(self.max_active, self._active)

    def _exit(self):
        with self._lock:
            self._active -= 1

    def execute(self, key, batch):
        self._enter()
        try:
            if self.delay:
                time.sleep(self.delay)
            self.batch_sizes.append(batch.shape[0])
            return self.inner.execute(key, batch)
        finally:
            self._exit()

    def execute_streamed(self, key, raw, strips=4):
        self._enter()
        try:
            if self.delay:
                time.sleep(self.delay)
            return self.inner.execute_streamed(key, raw, strips)
        finally:
            self._exit()


def _mk_req(loop, variant="fused3", deadline_ms=None, priority=0):
    return FocusRequest(
        raw=np.zeros((2, 2), np.complex64), scene=CFG, variant=variant,
        precision=None, future=loop.create_future(), t_submit=svc_now(),
        deadline_ms=deadline_ms, priority=priority)


def test_stop_mid_drain_flushes_remaining_buckets_in_deadline_order():
    """Regression: STOP dequeued mid-drain used to break out before the
    final deadline sweep, and the post-loop flush walked the buckets in
    dict-insertion order. Remaining buckets must flush earliest-deadline
    first even on the shutdown path."""

    async def main():
        q = RequestQueue(16)
        order = []

        async def execute(key, reqs):
            order.append(key.variant)
            for r in reqs:
                r.future.set_result(None)

        b = MicroBatcher(q, execute, max_batch=8, max_delay_ms=1000.0)
        loop = asyncio.get_running_loop()
        # later deadline inserted FIRST: dict order would flush it first
        q.put(_mk_req(loop, "fused3", deadline_ms=500.0))
        q.put(_mk_req(loop, "omegak", deadline_ms=50.0))
        q.put_stop()
        await b.run()
        return order

    assert asyncio.run(main()) == ["omegak", "fused3"]


def test_deadline_request_not_starved_by_hot_competing_key():
    """EDF across buckets: a deadline-carrying request on a cold key
    flushes before a hotter (more-requests, earlier-arrival) key whose
    requests carry no deadline."""

    async def main():
        q = RequestQueue(64)
        order = []

        async def execute(key, reqs):
            order.append(key.variant)
            for r in reqs:
                r.future.set_result(None)

        # max_delay 0: every bucket's flush deadline fires immediately,
        # so the sweep ranks ALL buckets — pure EDF ordering
        b = MicroBatcher(q, execute, max_batch=8, max_delay_ms=0.0)
        loop = asyncio.get_running_loop()
        for _ in range(3):
            q.put(_mk_req(loop, "fused3"))          # hot, no deadline
        q.put(_mk_req(loop, "omegak", deadline_ms=80.0))
        q.put_stop()
        await b.run()
        return order

    assert asyncio.run(main()) == ["omegak", "fused3"]


def test_max_batch_one_degenerates_to_sequential_bit_identical():
    """max_batch=1 is the sequential path: every request is its own
    batch and every image equals its per-request Pipeline.run."""
    raw = scene()
    refs = [reference(), np.asarray(build_pipeline(CFG, "fused3").run(
        jnp.asarray(raw) * 0.5))]

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=1, max_delay_ms=50.0, precision=None),
            backend=fast_backend())
        await svc.start()
        outs = await asyncio.gather(svc.focus(raw, CFG),
                                    svc.focus(raw * 0.5, CFG),
                                    svc.focus(raw, CFG))
        await svc.stop()
        return outs, svc.metrics.snapshot()

    outs, snap = asyncio.run(main())
    assert snap["batch_size_hist"] == {1: 3}, snap
    assert np.array_equal(outs[0], refs[0])
    assert np.array_equal(outs[1], refs[1])
    assert np.array_equal(outs[2], refs[0])


def test_inflight_cap_backpressure_coalesces_backlog_bit_identical():
    """One lane, one in-flight slot: while batch 1 runs, arrivals park
    behind the cap and coalesce into a FULL batch — and both batches'
    images stay bit-identical to the per-request path."""
    raw = scene()
    ref = reference()
    backend = _RecordingBackend(fast_backend(), delay=0.3)

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=4, max_delay_ms=5.0, precision=None,
                          lanes=1, inflight_cap=1),
            backend=backend)
        await svc.start(warm=[(CFG, "fused3", None)])
        t1 = asyncio.ensure_future(svc.focus(raw, CFG))
        await asyncio.sleep(0.15)       # batch 1 in flight on the lane
        rest = [asyncio.ensure_future(svc.focus(raw, CFG))
                for _ in range(4)]
        outs = await asyncio.gather(t1, *rest)
        await svc.stop()
        return outs, svc.metrics.snapshot()

    outs, snap = asyncio.run(main())
    assert backend.max_active == 1          # the cap held
    assert snap["batch_size_hist"] == {1: 1, 4: 1}, snap
    for o in outs:
        assert np.array_equal(o, ref)


def test_continuous_batching_overlaps_batches_across_lanes():
    """Two different-key batches must run CONCURRENTLY on two lanes —
    the host/device overlap the worker pool exists for — with both
    images bit-identical to their per-request references."""
    raw = scene()
    ref3, refo = reference(), reference("omegak")
    backend = _RecordingBackend(fast_backend(), delay=0.3)

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=2, max_delay_ms=20.0, precision=None,
                          lanes=2, inflight_cap=2),
            backend=backend)
        await svc.start()
        outs = await asyncio.gather(
            svc.focus(raw, CFG), svc.focus(raw, CFG),
            svc.focus(raw, CFG, variant="omegak"),
            svc.focus(raw, CFG, variant="omegak"))
        await svc.stop()
        return outs, svc.metrics.snapshot()

    outs, snap = asyncio.run(main())
    assert backend.max_active == 2          # batches genuinely overlapped
    assert snap["batch_size_hist"] == {2: 2}, snap
    assert sum(snap["lane_batches"].values()) == 2
    assert len(snap["lane_batches"]) == 2   # routed to distinct lanes
    assert np.array_equal(outs[0], ref3)
    assert np.array_equal(outs[1], ref3)
    assert np.array_equal(outs[2], refo)
    assert np.array_equal(outs[3], refo)


def test_past_deadline_request_dropped_with_request_cancelled():
    """A request whose deadline expires while still bucketed is dropped
    before padding — its future raises RequestCancelled and no device
    work happens for it."""
    raw = scene()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=4, max_delay_ms=400.0, precision=None),
            backend=fast_backend())
        await svc.start()
        with pytest.raises(RequestCancelled, match="deadline_ms=50"):
            await svc.focus(raw, CFG, deadline_ms=50.0)
        await svc.stop()
        return svc.metrics.snapshot()

    snap = asyncio.run(main())
    assert snap["cancelled"] == 1
    assert snap["deadline_dropped"] == 1
    assert snap["deadline_miss_rate"] == 1.0
    assert snap["batch_size_hist"] == {}    # nothing reached a lane


def test_client_cancelled_request_dropped_before_dispatch():
    raw = scene()
    ref = reference()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=4, max_delay_ms=200.0, precision=None),
            backend=fast_backend())
        await svc.start()
        t_cancel = asyncio.ensure_future(svc.focus(raw * 0.5, CFG))
        t_keep = asyncio.ensure_future(svc.focus(raw, CFG))
        await asyncio.sleep(0.05)           # both bucketed, flush at 200ms
        t_cancel.cancel()
        out = await t_keep
        with pytest.raises(asyncio.CancelledError):
            await t_cancel
        await svc.stop()
        return out, svc.metrics.snapshot()

    out, snap = asyncio.run(main())
    assert snap["cancelled"] == 1
    assert snap["deadline_dropped"] == 0
    assert snap["batch_size_hist"] == {1: 1}    # cancelled never padded in
    assert np.array_equal(out, ref)


def test_overload_sheds_latest_deadline_pending_request():
    """At the admission bound, an earlier-deadline arrival evicts the
    latest-deadline pending request (RequestCancelled) instead of being
    rejected."""
    raw = scene()
    ref = reference()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=4, max_delay_ms=400.0, precision=None,
                          max_queue=1),
            backend=fast_backend())
        await svc.start()
        victim = asyncio.ensure_future(svc.focus(raw * 0.5, CFG))
        await asyncio.sleep(0.05)           # victim bucketed: backlog = 1
        out = await svc.focus(raw, CFG, deadline_ms=5000.0)
        with pytest.raises(RequestCancelled, match="shed under overload"):
            await victim
        await svc.stop()
        return out, svc.metrics.snapshot()

    out, snap = asyncio.run(main())
    assert snap["shed"] == 1
    assert snap["rejected"] == 0
    assert np.array_equal(out, ref)


def test_worker_pool_routing_and_cost_weights():
    pool = WorkerPool(lanes=2, inflight_cap=2)
    k = BatchKey(CFG, "fused3", None, False)
    ks = BatchKey(CFG, "fused3", None, True)
    assert pool.route(ks) is pool.stream_lane
    assert pool.route(k) is pool.batch_lanes[0]     # tie -> lowest lane
    # the roofline prices lane load: bigger batches and bigger scenes
    # weigh more
    assert pool.predicted_seconds(k, batch=1) > 0
    assert (pool.predicted_seconds(k, batch=8)
            > pool.predicted_seconds(k, batch=1))
    big = BatchKey(make_test_scene(512), "fused3", None, False)
    assert pool.predicted_seconds(big) > pool.predicted_seconds(k)
    # a backlogged lane loses the next batch to the idle one
    pool.batch_lanes[0].backlog_s = 10.0
    assert pool.route(k) is pool.batch_lanes[1]


def test_snr_gate_rejects_out_of_gate_precision():
    raw = scene()

    async def main(deviation):
        svc = FocusService(
            ServiceConfig(max_batch=1, snr_gate_db=0.1),
            backend=fast_backend(),
            precision_deviation=lambda p: deviation)
        await svc.start()
        try:
            out = await svc.focus(raw, CFG, precision="bs16")
        finally:
            await svc.stop()
        return out, svc.metrics.snapshot()

    with pytest.raises(SnrGateViolation, match="0.1 dB gate"):
        asyncio.run(main(0.5))

    out, snap = asyncio.run(main(0.05))
    assert snap["gate_rejected"] == 0
    # the precision threads through to the compiled kernels
    assert not np.array_equal(out, reference())
    assert np.array_equal(
        out, np.asarray(build_pipeline(CFG, "fused3",
                                       precision="bs16").run(
            jnp.asarray(raw))))


def test_f32_requests_never_consult_the_gate():
    """The verification path — precision=None default tier disabled, or
    an explicit 'f32' request — must never trigger a gate measurement."""
    raw = scene()

    def boom(p):
        raise AssertionError("gate consulted for f32")

    async def main():
        svc = FocusService(ServiceConfig(max_batch=1, precision=None),
                           backend=fast_backend(), precision_deviation=boom)
        await svc.start()
        a = await svc.focus(raw, CFG)
        b = await svc.focus(raw, CFG, precision="f32")
        await svc.stop()
        return a, b

    a, b = asyncio.run(main())
    ref = reference()
    assert np.array_equal(a, ref)
    assert np.array_equal(b, ref)


def test_default_serving_tier_is_bs16():
    """Out of the box the service serves the device's tier, on the CPU
    the block-scaled throughput tier: an un-annotated request resolves to
    'bs16' — still gated — and an explicit precision='f32' request
    takes the full-precision verification path. Both ride the fused1
    route, so each must equal its per-axis fused3 reference bit-exact."""
    raw = scene()

    async def main():
        svc = FocusService(ServiceConfig(max_batch=1),
                           backend=fast_backend(),
                           precision_deviation=lambda p: 0.05)
        await svc.start()
        tier = await svc.focus(raw, CFG)
        verify = await svc.focus(raw, CFG, precision="f32")
        await svc.stop()
        return tier, verify

    tier, verify = asyncio.run(main())
    assert np.array_equal(tier, reference(precision="bs16"))
    assert np.array_equal(verify, reference())
    assert not np.array_equal(tier, verify)


@pytest.mark.parametrize("kind,tier", [("cpu", "bs16"),
                                       ("TPU v5 lite", "f32")])
def test_default_tier_is_chosen_per_device(monkeypatch, kind, tier):
    """ServiceConfig.precision='device' (the default) resolves once, from
    the device table, to the device's serving tier; v5e's MXU takes no
    f16 operands, so its tier is f32. An explicit precision is kept."""
    from repro.kernels import fft4step
    monkeypatch.setattr(fft4step, "device_kind", lambda: kind)
    assert FocusService(ServiceConfig(),
                        backend=fast_backend()).default_precision == tier
    assert FocusService(ServiceConfig(precision="bf16"),
                        backend=fast_backend()).default_precision == "bf16"


def test_service_restarts_after_stop():
    """stop() tears down the device executor; start() must rebuild it so
    the same FocusService instance can serve again."""
    raw = scene()

    async def main():
        svc = FocusService(ServiceConfig(max_batch=1, precision=None),
                           backend=fast_backend())
        await svc.start()
        a = await svc.focus(raw, CFG)
        await svc.stop()
        await svc.start()
        b = await svc.focus(raw, CFG)
        await svc.stop()
        return a, b

    a, b = asyncio.run(main())
    ref = reference()
    assert np.array_equal(a, ref)
    assert np.array_equal(b, ref)


def test_focus_rejected_when_service_not_running():
    raw = scene()

    async def main():
        svc = FocusService(ServiceConfig(max_batch=1, precision=None),
                           backend=fast_backend())
        with pytest.raises(RuntimeError, match="not running"):
            await svc.focus(raw, CFG)          # never started
        await svc.start()
        out = await svc.focus(raw, CFG)
        await svc.stop()
        with pytest.raises(RuntimeError, match="not running"):
            await svc.focus(raw, CFG)          # after stop
        return out

    assert np.array_equal(asyncio.run(main()), reference())


def test_halo_schedule_rejects_unsupported_options():
    """The halo schedule must refuse precision/turn_dtype rather than
    silently serving unlabelled f32 results."""
    from repro.core.sar.distributed import build_sharded
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with pytest.raises(ValueError, match="precision"):
        build_sharded(CFG, "fused3", mesh, schedule="halo",
                      precision="bf16")
    with pytest.raises(ValueError, match="turn_dtype"):
        build_sharded(CFG, "fused3", mesh, schedule="halo",
                      turn_dtype=jnp.bfloat16)


# ---------------------------------------------------------------------------
# Route invisibility
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _per_axis_reference(precision):
    kw = {} if precision is None else {"precision": precision}
    return np.asarray(build_pipeline(CFG, "fused3", **kw).run(
        jnp.asarray(scene())))


@pytest.mark.parametrize("precision", [None, "bf16", "f16", "bs16"])
@pytest.mark.parametrize("sharded", ["auto", "off"])
@pytest.mark.parametrize("fused1", ["auto", "off"])
def test_route_invisibility_matrix(fused1, sharded, precision):
    """Serving output must be IDENTICAL whichever route the backend
    picks: fused1 megakernel vs three per-axis dispatches, sharded twin
    enabled or pinned off, at every precision — bs16 included, whose
    per-line exponents are carried through the in-kernel corner turns
    precisely so this matrix holds bit-for-bit."""
    from repro.service.queue import BatchKey
    raw = np.asarray(scene(), np.complex64)[None]
    backend = LocalBackend(sweep=((None, None),), fused1=fused1,
                           sharded=sharded)
    out = backend.execute(BatchKey(CFG, "fused3", precision, False), raw)
    np.testing.assert_array_equal(out[0], _per_axis_reference(precision))


# ---------------------------------------------------------------------------
# Streaming route
# ---------------------------------------------------------------------------

def test_over_budget_scene_takes_streaming_route():
    raw = scene()
    ref = reference()

    async def main():
        svc = FocusService(
            ServiceConfig(max_batch=4, max_delay_ms=200.0, precision=None,
                          device_budget_bytes=raw.nbytes - 1),
            backend=fast_backend())
        await svc.start()
        outs = await asyncio.gather(svc.focus(raw, CFG),
                                    svc.focus(raw, CFG))
        await svc.stop()
        return outs, svc.metrics.snapshot()

    outs, snap = asyncio.run(main())
    assert snap["streamed"] == 2            # never coalesced
    for o in outs:
        assert np.array_equal(o, ref)       # streamed == in-memory


# ---------------------------------------------------------------------------
# Metrics artifact
# ---------------------------------------------------------------------------

def test_service_metrics_emit_valid_schema2_bench_doc(tmp_path):
    raw = scene()

    async def main():
        svc = FocusService(ServiceConfig(max_batch=2, max_delay_ms=100.0,
                                         precision=None),
                           backend=fast_backend())
        await svc.start()
        await asyncio.gather(svc.focus(raw, CFG), svc.focus(raw, CFG))
        await svc.stop()
        return svc

    svc = asyncio.run(main())
    doc = svc.metrics.to_bench_doc(section="service_test")
    validate_bench_doc(doc)                 # schema 2, ISO-8601 stamp
    path = tmp_path / "BENCH_service_test.json"
    svc.metrics.write_bench_json(str(path))
    validate_bench_file(str(path))
    snap = svc.metrics.snapshot()
    assert snap["completed"] == 2
    assert snap["latency_p99_ms"] >= snap["latency_p50_ms"] > 0
    # worker-pool observability: batch-fill histogram (exact "k/max"
    # keys) and the per-lane occupancy row, all inside the validated doc
    assert snap["batch_fill_hist"] == {"2/2": 1}
    assert sum(snap["lane_batches"].values()) == 1
    assert set(snap["lane_occupancy"]) == {"fused0", "fused1", "stream"}
    rows = {r["name"]: r for r in doc["rows"]}
    assert "lanes=3" in rows["lanes"]["derived"]
    assert "occ_fused0=" in rows["lanes"]["derived"]
    assert "fill_hist=" in rows["batching"]["derived"]
    assert "goodput_rps=" in rows["throughput"]["derived"]
    assert "deadline_miss_rate=" in rows["throughput"]["derived"]


def test_serve_ratchet_gates_load_replay_structure():
    """scripts/bench_compare.py --serve must gate the deterministic
    load-replay structure: lane count may not shrink, the smoke
    deadline-miss rate may not grow, and the goodput-gain row (plus the
    family itself) must exist. The chaos family is gated the same way:
    zero lost requests, every scheduled seam fired, goodput ratio at or
    above its bar, family presence."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_compare_script",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def doc(lanes=3, miss="0.0000", with_gain=True, with_smoke=True,
            lost=0, seams=3, ratio="0.84x", with_chaos=True):
        rows = [
            {"section": "t", "name": "serve_tier_gate_bs16", "wall_ms": 0.0,
             "derived": "snr_deviation_db=0.0026;gate_db=0.1;admitted=True"},
            {"section": "t", "name": "serve_tier_bs16_burst_B4_per_request",
             "wall_ms": 1.0, "derived": ""},
            {"section": "t", "name": "serve_load_burst_replay",
             "wall_ms": 1.0, "derived": "goodput_rps=10.0"},
        ]
        if with_gain:
            rows.append({"section": "t", "name": "serve_load_goodput_gain",
                         "wall_ms": 0.0,
                         "derived": "gain_vs_single_flight=2.00x;bar=1.5x"})
        if with_smoke:
            rows.append({"section": "t", "name": "serve_load_smoke",
                         "wall_ms": 0.0,
                         "derived": f"lanes={lanes};"
                                    f"deadline_miss_rate={miss}"})
        if with_chaos:
            rows.append({"section": "t", "name": "serve_chaos_smoke",
                         "wall_ms": 0.0,
                         "derived": f"lost={lost};completed=24;requests=24;"
                                    f"seams={seams}"})
            rows.append({"section": "t",
                         "name": "serve_chaos_goodput_ratio",
                         "wall_ms": 0.0,
                         "derived": f"ratio_vs_fault_free={ratio};"
                                    "bar=0.5x"})
        return {"rows": rows}

    base = doc()
    assert mod.compare_serve(base, doc()) == []
    assert any("lane count shrank" in f
               for f in mod.compare_serve(base, doc(lanes=2)))
    assert any("deadline_miss_rate grew" in f
               for f in mod.compare_serve(base, doc(miss="0.2500")))
    assert any("goodput_gain row missing" in f
               for f in mod.compare_serve(base, doc(with_gain=False)))
    no_loads = {"rows": [r for r in doc()["rows"]
                         if not r["name"].startswith("serve_load_")]}
    assert any("load-replay family is gone" in f
               for f in mod.compare_serve(base, no_loads))
    # chaos structure: lost requests, missing seams, a sunk goodput
    # ratio, and dropping the family outright all fail the ratchet
    assert any("lost under the seeded fault replay" in f
               for f in mod.compare_serve(base, doc(lost=2)))
    assert any("fault seams fired" in f
               for f in mod.compare_serve(base, doc(seams=2)))
    assert any("recovery overhead regressed" in f
               for f in mod.compare_serve(base, doc(ratio="0.30x")))
    assert any("chaos-replay family is gone" in f
               for f in mod.compare_serve(base, doc(with_chaos=False)))
    # lane GROWTH and new rows land freely (ratchet, not a freeze)
    assert mod.compare_serve(base, doc(lanes=4)) == []


def test_write_bench_json_schema2_and_validation(tmp_path):
    path = str(tmp_path / "BENCH_x.json")
    rows = [{"section": "s", "name": "n", "wall_ms": 1.0, "derived": ""}]
    write_bench_json(path, rows, smoke=True)
    doc = validate_bench_file(path)
    assert doc["schema"] == 2 and "generated_unix" not in doc
    with pytest.raises(ValueError, match="schema"):
        validate_bench_doc({**doc, "schema": 1})
    with pytest.raises(ValueError, match="ISO-8601"):
        validate_bench_doc({**doc, "generated_utc": 1234.5})
    with pytest.raises(ValueError, match="wall_ms"):
        validate_bench_doc({**doc, "rows": [{"section": "s", "name": "n"}]})


# ---------------------------------------------------------------------------
# Sharded backend
# ---------------------------------------------------------------------------

def test_sharded_backend_reachable_and_matches_local():
    """The sharded backend through the service API (single host device:
    a 1-device mesh — the wiring, specs, and collectives all execute)."""
    raw = scene()
    ref = reference()

    async def main():
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        svc = FocusService(
            ServiceConfig(backend="sharded", max_batch=2,
                          max_delay_ms=200.0, precision=None),
            backend=ShardedBackend(mesh=mesh))
        await svc.start()
        outs = await asyncio.gather(svc.focus(raw, CFG),
                                    svc.focus(raw, CFG))
        await svc.stop()
        return outs, svc.metrics.snapshot()

    outs, snap = asyncio.run(main())
    assert snap["batch_size_hist"] == {2: 1}
    for o in outs:
        assert np.array_equal(o, ref)


@pytest.mark.slow
def test_sharded_backend_parity_8_devices():
    """Subprocess (8 fake CPU devices): the service's sharded backend —
    generic corner-turn lowering AND the halo schedule — vs the local
    backend at <= 0.1 dB (the generic lowering is in fact bit-identical,
    and reproduces hand-written corner2 exactly)."""
    code = """
import asyncio, numpy as np, jax, jax.numpy as jnp
from repro.core.sar import build_pipeline, paper_targets, simulate_cached, metrics
from repro.core.sar.geometry import test_scene
from repro.core.sar.distributed import build_corner2, lower_pipeline
from repro.service import FocusService, ServiceConfig, ShardedBackend

cfg = test_scene(256)
targets = paper_targets(cfg)
raw = simulate_cached(cfg, targets)
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

local = np.asarray(build_pipeline(cfg, "fused3").run(jnp.asarray(raw)))

# generic plan lowering == hand-written corner2, bit for bit
pipe = build_pipeline(cfg, "fused3")
gen = np.asarray(pipe.lower_sharded(mesh)(jnp.asarray(raw)))
c2 = np.asarray(build_corner2(cfg, mesh)(jnp.asarray(raw)))
assert np.array_equal(gen, c2), "generic lowering != corner2"
assert np.array_equal(gen, local), "generic lowering != local pipeline"

async def serve(schedule, variant):
    svc = FocusService(
        ServiceConfig(backend="sharded", max_batch=2, max_delay_ms=200.0,
                      precision=None),
        backend=ShardedBackend(mesh=mesh, schedule=schedule))
    await svc.start()
    outs = await asyncio.gather(svc.focus(raw, cfg, variant=variant),
                                svc.focus(raw, cfg, variant=variant))
    await svc.stop()
    return outs

outs = asyncio.run(serve("corner2", "fused3"))
for o in outs:
    assert np.array_equal(o, local), "service sharded != local"

# halo: paper-ordered RDA with one corner turn + ring-halo RCMC; parity
# gate vs the local unfused reference
un = np.asarray(build_pipeline(cfg, "unfused").run(jnp.asarray(raw)))
outs_h = asyncio.run(serve("halo", "fused3"))
for o in outs_h:
    c = metrics.compare_pipelines(o, un, cfg, targets)
    assert max(c["snr_delta_db"]) <= 0.1, c["snr_delta_db"]
print("SERVICE_SHARDED_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC + os.pathsep + os.path.join(SRC, ".."))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SERVICE_SHARDED_OK" in r.stdout
