"""The sharded megakernel lowering (ISSUE 7): mega steps through
shard_map — one megakernel dispatch per device per phase group, in-kernel
corner turns becoming all_to_all collectives.

Fast tests cover the pure-math pieces (the corner-turn permutation
property, the collective-bytes cost terms, the routing predicate, the
mesh helper, the compiler's per-segment payload record). The 8-device
parity suite runs in subprocesses (`run_sub`) under the slow marker —
CI's multi-device job executes it with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    from tests._hypothesis_fallback import given, settings, strategies as st

from tests.test_distributed import run_sub

from repro.tuning import cost
from repro.tuning.space import ScheduleProblem, SegmentShape


# ---------------------------------------------------------------------------
# Property: the corner turn is a pure permutation
# ---------------------------------------------------------------------------
#
# A numpy model of jax.lax.all_to_all(tiled=True): each device splits its
# local slab into P parts along split_axis, sends part e to device e, and
# concatenates what it receives along concat_axis. The lowering's claim is
# that shard -> turn -> unshard moves every element to where a plain
# re-shard along the other axis would put it — a permutation, no
# arithmetic — so f32 bit-identity of the sharded pipeline follows from
# per-slab kernel bit-identity.

def _np_all_to_all(slabs, split_axis, concat_axis):
    p = len(slabs)
    parts = [np.array_split(s, p, axis=split_axis) for s in slabs]
    return [np.concatenate([parts[e][d] for e in range(p)],
                           axis=concat_axis) for d in range(p)]


def _shard(x, axis, p):
    return np.array_split(x, p, axis=axis)


def _unshard(slabs, axis):
    return np.concatenate(slabs, axis=axis)


@settings(max_examples=40, deadline=None)
@given(b=st.integers(1, 3),
       na_blocks=st.integers(1, 6),
       nr_blocks=st.integers(1, 6),
       p=st.sampled_from([1, 2, 4, 8]),
       stream=st.sampled_from([0, 1]),
       batched=st.sampled_from([False, True]))
def test_corner_turn_is_permutation_identity(b, na_blocks, nr_blocks, p,
                                             stream, batched):
    """shard(stream) -> all_to_all -> unshard(other) == identity, for
    arbitrary (B, na, nr) and any device count dividing the sharded axis
    — and a second turn restores the original sharding exactly."""
    na, nr = p * na_blocks, p * nr_blocks
    shape = (b, na, nr) if batched else (na, nr)
    bpre = len(shape) - 2
    x = np.arange(int(np.prod(shape)), dtype=np.int64).reshape(shape)

    slabs = _shard(x, bpre + stream, p)
    # the lowering's _turn: split the OTHER scene axis, concat the current
    split_axis = bpre + (1 - stream)
    concat_axis = bpre + stream
    turned = _np_all_to_all(slabs, split_axis, concat_axis)
    np.testing.assert_array_equal(
        _unshard(turned, bpre + (1 - stream)), x)
    # turning back is the inverse permutation
    back = _np_all_to_all(turned, concat_axis, split_axis)
    np.testing.assert_array_equal(_unshard(back, bpre + stream), x)


@settings(max_examples=40, deadline=None)
@given(na_blocks=st.integers(1, 6),
       nr_blocks=st.integers(1, 6),
       p=st.sampled_from([1, 2, 4, 8]),
       stream=st.sampled_from([0, 1]))
def test_carried_exponent_turn_is_pair_permutation(na_blocks, nr_blocks, p,
                                                   stream):
    """The bs16 carried-exponent corner turn is a pure permutation of
    (value, exponent) pairs: the data slab rides all_to_all while its
    per-line exponents ride all_gather along the OLD stream axis
    (distributed.lower_pipeline). Applying each line's exponent before
    the turn (per-shard exponent slices) and after it (gathered vector
    broadcast over the now-full stream axis) must reassemble the same
    image — no pair is split, scaled twice, or dropped."""
    na, nr = p * na_blocks, p * nr_blocks
    x = np.arange(na * nr, dtype=np.float64).reshape(na, nr) + 1.0
    n_lines = na if stream == 0 else nr
    e = np.arange(n_lines, dtype=np.float64) % 7 - 3   # per-line exponents
    ecol = e.reshape(-1, 1) if stream == 0 else e.reshape(1, -1)
    want = x * 2.0 ** ecol

    slabs = _shard(x, stream, p)
    eslabs = _shard(ecol, stream, p)
    # before the turn each device holds its own lines' exponents
    pre = [s * 2.0 ** es for s, es in zip(slabs, eslabs)]
    np.testing.assert_array_equal(_unshard(pre, stream), want)
    # the turn: data all_to_all, exponents all_gather (tiled concat)
    turned = _np_all_to_all(slabs, 1 - stream, stream)
    egather = _unshard(eslabs, stream)       # full vector on every device
    post = [t * 2.0 ** egather for t in turned]
    np.testing.assert_array_equal(_unshard(post, 1 - stream), want)


# ---------------------------------------------------------------------------
# Cost model: the collective-bytes terms
# ---------------------------------------------------------------------------

MEGA_SEGS = (SegmentShape(axis=0, fwd=True),
             SegmentShape(axis=1, fwd=True, inv=True, filtered=True),
             SegmentShape(axis=0, inv=True, filtered=True))


def test_collective_turn_bytes_matches_doc_math():
    """docs/distributed.md: one turn moves 2·4·na·nr·(P-1)/P bytes per
    split-f32 re/im pair per device."""
    na = nr = 4096
    p = 8
    slab = 2 * 4 * na * nr // p                       # re+im local slab
    assert cost.collective_turn_bytes(na, nr, devices=p) == slab * 7 // 8
    # bf16 wire format halves it
    assert cost.collective_turn_bytes(na, nr, devices=p, elem_bytes=2) \
        == slab * 7 // 16
    # one device: nothing crosses links
    assert cost.collective_turn_bytes(na, nr, devices=1) == 0


def test_turn_seconds_sharded_is_collective_priced():
    local = ScheduleProblem.mega_2d(2048, 2048, MEGA_SEGS)
    shard = ScheduleProblem.mega_2d(2048, 2048, MEGA_SEGS, devices=8)
    # sharded turns cost wire time even for VMEM-resident slabs...
    assert cost.turn_seconds(local, residency="vmem") == 0.0
    assert cost.turn_seconds(shard, residency="vmem") > 0.0
    # ...and depth>=2 double-buffering earns the overlap credit
    full = cost.turn_seconds(shard, residency="staged", buffer_depth=1)
    overlapped = cost.turn_seconds(shard, residency="staged",
                                   buffer_depth=2)
    assert overlapped == pytest.approx(full * cost.TURN_OVERLAP)


def test_sharded_problem_divides_lines_not_transforms():
    shard = ScheduleProblem.mega_2d(2048, 1024, MEGA_SEGS, devices=8)
    range_seg, az_seg = MEGA_SEGS[1], MEGA_SEGS[0]
    assert shard.seg_n(range_seg) == 1024              # transform whole
    assert shard.seg_lines(range_seg) == 2048 // 8     # free axis 1/P
    assert shard.seg_n(az_seg) == 2048
    assert shard.seg_lines(az_seg) == 1024 // 8
    with pytest.raises(ValueError, match="devices"):
        ScheduleProblem.mega_2d(100, 100, MEGA_SEGS, devices=8)


def test_sharded_preferred_routes_big_scenes_only():
    # VMEM-fitting scenes keep the local single-dispatch route
    assert not cost.sharded_preferred(512, 512, devices=8)
    # the paper scale shards
    assert cost.sharded_preferred(4096, 4096, devices=8)
    assert cost.sharded_preferred(1024, 1024, devices=8)
    # degenerate meshes / non-tiling scenes never route
    assert not cost.sharded_preferred(4096, 4096, devices=1)
    assert not cost.sharded_preferred(4100, 4100, devices=8)


def test_schedule_frontier_ranks_sharded_schedules():
    """The graph search prices devices>1 problems end-to-end: the
    frontier comes back non-empty, cost-ascending, and cheaper than the
    identical local problem (1/P compute + slab terms dominate the added
    wire cost at paper scale)."""
    from repro.tuning.search import schedule_frontier
    shard = ScheduleProblem.mega_2d(4096, 4096, MEGA_SEGS, devices=8)
    local = ScheduleProblem.mega_2d(4096, 4096, MEGA_SEGS)
    ranked = schedule_frontier(shard, k=4)
    assert ranked
    costs = [cost.schedule_seconds(s, shard) for s in ranked]
    assert costs == sorted(costs)
    best_local = min(cost.schedule_seconds(s, local)
                     for s in schedule_frontier(local, k=4))
    assert costs[0] < best_local


# ---------------------------------------------------------------------------
# Compiler + lowering surface (single device, tier-1)
# ---------------------------------------------------------------------------

def test_mega_step_records_per_segment_payloads():
    from repro.core import plan as planlib
    from repro.core.sar.geometry import test_scene
    p = planlib.build_variant(test_scene(256), "fused1", tune="off")
    step = p.steps[0]
    assert step.kind == "mega"
    segs = step.kernel_kw["segments"]
    assert step.seg_filter_args is not None
    assert len(step.seg_filter_args) == len(segs)
    # flat mega_spectral_op order == concatenation of per-segment tuples
    flat = [a for fa in step.seg_filter_args for a in fa]
    modes = [rec[3] for rec in segs]
    per_mode = {"none": 0, "shared": 2, "full": 2, "outer": 2,
                "shared_outer": 4}
    assert len(flat) == sum(per_mode[m] for m in modes)


def test_make_sar_mesh_single_host():
    import jax
    from repro.core.sar.distributed import make_sar_mesh
    mesh = make_sar_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.devices.size == len(jax.devices())
    with pytest.raises(ValueError, match="axis names"):
        make_sar_mesh(axes=("a", "b", "c"))


# ---------------------------------------------------------------------------
# 8-device parity (slow, subprocess — the CI multi-device job)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_mega_parity_8_devices():
    """The acceptance criterion: 8 devices, one megakernel dispatch per
    device per phase group (3 groups, 2 collective turns), f32
    bit-identical to the LOCAL per-axis reference for fused1/csa_fused1
    and <= 0.1 dB for omegak_fused1 — in both residency modes and
    batched."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.sar import test_scene, paper_targets, simulate, metrics
from repro.core import plan as planlib
import repro.core.sar.csa, repro.core.sar.omegak  # register variants

cfg = test_scene(256)
targets = paper_targets(cfg)
raw = jnp.asarray(simulate(cfg, targets))
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

for variant, twin in (("fused1", "fused3"), ("csa_fused1", "csa_fused"),
                      ("omegak_fused1", "omegak")):
    run = planlib.build_variant(cfg, variant, tune="off").lower_sharded(mesh)
    assert run.devices == 8 and run.dispatches_per_device == 3 \
        and run.turns == 2, (run.devices, run.dispatches_per_device,
                             run.turns)
    img = np.asarray(run(raw))
    ref = np.asarray(planlib.build_variant(cfg, twin, tune="off").run(raw))
    if variant == "omegak_fused1":
        c = metrics.compare_pipelines(img, ref, cfg, targets)
        assert max(c["snr_delta_db"]) <= 0.1, c["snr_delta_db"]
    else:
        assert np.array_equal(img, ref), variant
    # the sharded image also matches the LOCAL megakernel bit-for-bit
    mega = np.asarray(planlib.build_variant(cfg, variant, tune="off").run(raw))
    assert np.array_equal(img, mega), variant

# staged residency: per-device DMA-staged megakernels, same bits
p1 = planlib.build_variant(cfg, "fused1", tune="off")
run_s = p1.lower_sharded(mesh, residency="staged")
assert [u["residency"] for u in run_s.unit_info] == ["staged"] * 3
ref = np.asarray(planlib.build_variant(cfg, "fused3", tune="off").run(raw))
assert np.array_equal(np.asarray(run_s(raw)), ref)

# batched (B, na, nr): one lowering, same bits per scene
rawb = jnp.stack([raw, 2 * raw])
run_b = p1.lower_sharded(mesh)
refb = np.asarray(planlib.build_variant(cfg, "fused3", tune="off").run(rawb))
assert np.array_equal(np.asarray(run_b(rawb)), refb)

# multi-host-shaped mesh path: processes x local devices layout
from repro.core.sar.distributed import make_sar_mesh
mesh2 = make_sar_mesh(axes=("pod", "data"))
assert mesh2.devices.shape[0] == 1          # single-host: 1 x 8
run2 = p1.lower_sharded(mesh2, axes=("pod", "data"))
assert np.array_equal(np.asarray(run2(raw)), ref)
print("SHARDED_MEGA_OK")
""")
    assert "SHARDED_MEGA_OK" in out


@pytest.mark.slow
def test_sharded_service_route_8_devices():
    """LocalBackend.execute_streamed routes a big (locally-staged) scene
    to the sharded megakernel twin when the cost model prefers it — and
    the served image is bit-identical to the per-axis reference, so the
    route is invisible."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.sar import test_scene
from repro.core import plan as planlib
from repro.service.backends import LocalBackend
from repro.service.queue import BatchKey
from repro.tuning import cost

cfg = test_scene(1024)
assert cost.mega_residency(cfg.na, cfg.nr) == "staged"  # over budget
key = BatchKey(cfg, "fused3", None, True)
rng = np.random.default_rng(0)
raw = (rng.standard_normal((1024, 1024))
       + 1j * rng.standard_normal((1024, 1024))).astype(np.complex64)

backend = LocalBackend()
assert backend._sharded_twin(key) == "fused1"
img = backend.execute_streamed(key, raw)
assert key in backend._sharded_fns            # the sharded path ran
ref = np.asarray(planlib.build_variant(cfg, "fused3", tune="off")
                 .run(jnp.asarray(raw)))
assert np.array_equal(img, ref)

# opting out pins the host-strip path
off = LocalBackend(sharded="off")
assert off._sharded_twin(key) is None
print("SHARDED_ROUTE_OK")
""")
    assert "SHARDED_ROUTE_OK" in out
