"""fused1: the true single-dispatch 2-D SAR megakernel.

Covers the ISSUE-5 acceptance gates: the compiler invariant
(``dispatches == 1`` under the cross-axis grammar), f32 bit-identity to
the 3-dispatch ``fused3`` pipeline, scratch-staged vs VMEM-resident
equivalence, the narrow-precision SNR gate, the execution-surface guards
(``run_streamed`` / ``lower_sharded`` must reject a cross-axis step),
and the serving route that sends VMEM-fitting scenes through fused1.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.core.plan import FUSE_MEGA, SpectralPlan, Stage, \
    plan_dispatch_count
from repro.core.sar import (
    build_pipeline,
    documented_dispatches,
    metrics,
    paper_targets,
    simulate_cached,
)
from repro.core.sar.geometry import test_scene as make_test_scene
from repro.core.sar.rda import plan_fused1, plan_fused3
from repro import tuning

CFG = make_test_scene(256)
TARGETS = paper_targets(CFG)

FUSED1_VARIANTS = ("fused1", "csa_fused1", "omegak_fused1")


def scene():
    return jnp.asarray(simulate_cached(CFG, TARGETS))


# ---------------------------------------------------------------------------
# Compiler invariants: the cross-axis grammar
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", FUSED1_VARIANTS)
def test_fused1_compiles_to_one_dispatch(variant):
    """The acceptance criterion: every fused1 variant is EXACTLY one
    dispatch, as a static plan property and as the compiled pipeline."""
    var = planlib.get_variant(variant)
    assert var.dispatches == 1
    assert plan_dispatch_count(var.plan_fn(), fuse=FUSE_MEGA) == 1
    p = build_pipeline(CFG, variant, tune="off")
    assert p.dispatches == documented_dispatches(variant) == 1
    assert p.hbm_roundtrips == 1
    assert p.steps[0].kind == "mega"


def test_mega_grammar_segment_rules_still_hold():
    """Cross-axis fusion must not relax the per-axis grammar: within a
    segment an ifft still closes and an fft still only opens — but an
    axis change always opens a fresh segment."""
    # fft(1) then fft(1): two dispatches even under mega
    two_ffts = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True),
        Stage("b", axis=1, fwd=True),
    ))
    assert plan_dispatch_count(two_ffts, fuse=FUSE_MEGA) == 2
    # mul after ifft on the SAME axis: still two
    mul_after_inv = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True, inv=True, filters=("range_mf",)),
        Stage("b", axis=1, filters=("range_mf",)),
    ))
    assert plan_dispatch_count(mul_after_inv, fuse=FUSE_MEGA) == 2
    # but fft(1) then fft(0) — an axis change — is ONE megakernel dispatch
    cross = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True),
        Stage("b", axis=0, fwd=True),
    ))
    assert plan_dispatch_count(cross, fuse=FUSE_MEGA) == 1
    assert plan_dispatch_count(cross, fuse=True) == 2
    # transposes and custom stages stay walls under mega fusion too
    walled = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True),
        Stage("t", kind="transpose"),
        Stage("b", axis=0, inv=True),
    ))
    assert plan_dispatch_count(walled, fuse=FUSE_MEGA) == 3


def test_fused1_plan_matches_fused3_stages():
    """fused1 is the SAME stage list as fused3 — only the fusion level
    differs; the megakernel is a compilation strategy, not an algorithm."""
    a, b = plan_fused1(), plan_fused3()
    assert a.stages == b.stages
    assert plan_dispatch_count(a, fuse=True) == 3       # per-axis: 3
    assert plan_dispatch_count(a, fuse=FUSE_MEGA) == 1  # cross-axis: 1


# ---------------------------------------------------------------------------
# Numerics: bit-identity and residency-mode equivalence
# ---------------------------------------------------------------------------

def test_fused1_bit_identical_to_fused3_f32():
    """The megakernel runs the exact same per-segment math (same DFT
    constants, same filter application, same ordering), so collapsing
    3 dispatches to 1 must not move a single f32 bit."""
    a = np.asarray(build_pipeline(CFG, "fused1", tune="off").run(scene()))
    b = np.asarray(build_pipeline(CFG, "fused3", tune="off").run(scene()))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("variant", ["csa_fused1", "omegak_fused1"])
def test_fused1_family_bit_identical_to_per_axis(variant):
    twin = {"csa_fused1": "csa_fused", "omegak_fused1": "omegak"}[variant]
    a = np.asarray(build_pipeline(CFG, variant, tune="off").run(scene()))
    b = np.asarray(build_pipeline(CFG, twin, tune="off").run(scene()))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("variant", FUSED1_VARIANTS)
def test_staged_equals_vmem_resident(variant):
    """Both residency modes run identical per-segment math on different
    block partitions — every segment treats line blocks independently, so
    the DMA-staged image equals the VMEM-resident image bit-for-bit.
    (csa/omegak also exercise the FULL-filter DMA-slicing path.)"""
    a = np.asarray(build_pipeline(CFG, variant, tune="off",
                                  residency="vmem").run(scene()))
    b = np.asarray(build_pipeline(CFG, variant, tune="off",
                                  residency="staged",
                                  phase_block=32).run(scene()))
    np.testing.assert_array_equal(a, b)
    # a different phase split must not change the numerics either
    c = np.asarray(build_pipeline(CFG, variant, tune="off",
                                  residency="staged",
                                  phase_block=8).run(scene()))
    np.testing.assert_array_equal(a, c)


def test_fused1_batched_matches_unbatched():
    p = build_pipeline(CFG, "fused1", tune="off")
    raw = scene()
    out = np.asarray(p.run(jnp.stack([raw, 0.5 * raw])))
    one = np.asarray(p.run(raw))
    np.testing.assert_array_equal(out[0], one)
    scale = float(np.max(np.abs(one)))
    np.testing.assert_allclose(out[1], 0.5 * one, atol=1e-5 * scale, rtol=0)


def test_fused1_matches_xla_oracle():
    """The mega step compiled to the unfused jnp oracle chain agrees at
    f32 roundoff — the megakernel is the same math as 7 XLA ops."""
    a = np.asarray(build_pipeline(CFG, "fused1", tune="off").run(scene()))
    b = np.asarray(build_pipeline(CFG, "fused1", tune="off", backend="xla",
                                  fuse=FUSE_MEGA).run(scene()))
    assert metrics.l2_relative_error(a, b) < 1e-5


@pytest.mark.parametrize("precision", ["bf16", "bs16"])
def test_fused1_narrow_precision_snr_gate(precision):
    """Narrow matmul operands through the megakernel stay inside the
    serving quality gate: <= 0.1 dB per-target SNR deviation vs the
    fused1 f32 image (the same gate the service enforces per request)."""
    img32 = np.asarray(build_pipeline(CFG, "fused1", tune="off").run(scene()))
    imgN = np.asarray(build_pipeline(CFG, "fused1", tune="off",
                                     precision=precision).run(scene()))
    assert not np.array_equal(imgN, img32)
    c = metrics.compare_pipelines(imgN, img32, CFG, TARGETS)
    assert max(c["snr_delta_db"]) <= 0.1, c["snr_delta_db"]


# ---------------------------------------------------------------------------
# Execution-surface guards
# ---------------------------------------------------------------------------

def test_run_streamed_rejects_mega_step():
    """A cross-axis step has no single free axis to strip a host scene
    along — the streaming executor must refuse, not silently mis-slice."""
    p = build_pipeline(CFG, "fused1", tune="off")
    with pytest.raises(ValueError, match="streaming"):
        p.run_streamed(np.asarray(simulate_cached(CFG, TARGETS)), strips=4)


def test_lower_sharded_accepts_mega_step():
    """The shard_map lowering splits a mega step at its in-kernel turn
    boundaries into per-device segment groups: 3 megakernel dispatches
    per device, the 2 turns now collectives — and on a 1-device mesh the
    result stays bit-identical to the local fused3 reference."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    p = build_pipeline(CFG, "fused1", tune="off")
    run = p.lower_sharded(mesh)
    assert run.devices == 1
    assert run.dispatches_per_device == 3
    assert run.turns == 2
    assert all(u["kind"] == "mega" for u in run.unit_info)
    raw = scene()
    ref = np.asarray(build_pipeline(CFG, "fused3", tune="off").run(raw))
    np.testing.assert_array_equal(np.asarray(run(raw)), ref)


def test_lower_sharded_rejects_transposing_plan():
    """Transpose stages reorder the whole scene — no per-device slab can
    do that locally, and the error must say what to compile instead."""
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    p = build_pipeline(CFG, "fused", tune="off")   # transposing variant
    with pytest.raises(ValueError, match="fused1"):
        p.lower_sharded(mesh)


def test_mega_rejected_inside_transposed_section():
    bad = SpectralPlan("p", (
        Stage("t", kind="transpose"),
        Stage("a", axis=1, fwd=True),
        Stage("b", axis=0, inv=True),
        Stage("t2", kind="transpose"),
    ))
    with pytest.raises(ValueError, match="transposed"):
        planlib.compile_plan(bad, CFG, fuse=FUSE_MEGA)


# ---------------------------------------------------------------------------
# Residency selection: tuning knobs + the VMEM feasibility cut
# ---------------------------------------------------------------------------

def test_auto_residency_follows_vmem_budget():
    small = make_test_scene(256)
    assert tuning.cost.mega_residency(small.na, small.nr) == "vmem"
    assert tuning.cost.mega_residency(4096, 4096) == "staged"
    # the compiled step records the resolved mode
    p = build_pipeline(small, "fused1", tune="off")
    assert p.steps[0].kernel_kw["residency"] == "vmem"
    p = build_pipeline(small, "fused1", tune="off", residency="staged")
    assert p.steps[0].kernel_kw["residency"] == "staged"


def test_kernel_config_mega_knobs_validate_and_roundtrip():
    cfg = tuning.KernelConfig(residency="staged", phase_block=16)
    assert tuning.KernelConfig.from_dict(cfg.to_dict()) == cfg
    # the knobs never leak into the per-axis kernel kwargs
    assert "residency" not in cfg.spectral_kwargs()
    with pytest.raises(ValueError, match="residency"):
        tuning.KernelConfig(residency="hbm")
    with pytest.raises(ValueError, match="phase_block"):
        tuning.KernelConfig(phase_block=12)


# ---------------------------------------------------------------------------
# Serving route
# ---------------------------------------------------------------------------

def test_local_backend_routes_vmem_scenes_to_fused1():
    from repro.service.backends import FUSED1_TWINS, LocalBackend
    from repro.service.queue import BatchKey
    cfg = make_test_scene(128)
    raw = np.asarray(simulate_cached(cfg, paper_targets(cfg))
                     ).astype(np.complex64)
    key = BatchKey(cfg, "fused3", None, False)
    routed = LocalBackend(sweep=((None, None),))
    pinned = LocalBackend(sweep=((None, None),), fused1="off")
    assert FUSED1_TWINS["fused3"] == "fused1"
    assert routed._route_variant(key) == "fused1"
    assert pinned._route_variant(key) == "fused3"
    # the route is invisible to the caller: same images bit-for-bit
    np.testing.assert_array_equal(routed.execute(key, raw[None]),
                                  pinned.execute(key, raw[None]))
    # a scene past the VMEM budget keeps its per-axis variant
    big = make_test_scene(4096)
    assert routed._route_variant(
        BatchKey(big, "fused3", None, False)) == "fused3"
    # unknown-twin variants are never rerouted
    assert routed._route_variant(
        BatchKey(cfg, "fused", None, False)) == "fused"
    # block-scaled precisions route too: the megakernel carries per-line
    # exponents through its corner turns, so bs16 is bit-invisible as well
    assert routed._route_variant(
        BatchKey(cfg, "fused3", "bs16", False)) == "fused1"
    assert routed._route_variant(
        BatchKey(cfg, "fused3", "bf16", False)) == "fused1"


# ---------------------------------------------------------------------------
# Satellites that ride along with the megakernel
# ---------------------------------------------------------------------------

def test_dft_constants_memoized_per_factorization():
    """build_spectral_call / re-traces must hit the lru_cache instead of
    rebuilding the numpy DFT matrices."""
    from repro.kernels.fft4step import SpectralSpec, build_spectral_call, \
        dft_constants
    dft_constants.cache_clear()
    a = dft_constants(16, 8)
    before = dft_constants.cache_info()
    b = dft_constants(16, 8)
    after = dft_constants.cache_info()
    assert after.hits == before.hits + 1 and after.misses == before.misses
    assert all(x is y for x, y in zip(a, b))          # the SAME arrays
    assert not a[0].flags.writeable                    # shared -> read-only
    # two kernel builds for the same spec: second build misses nothing
    spec = SpectralSpec(n=128, fwd=True, filter_mode="none", inv=False)
    build_spectral_call(spec, lines=8, interpret=True)
    misses = dft_constants.cache_info().misses
    build_spectral_call(spec, lines=8, interpret=True)
    assert dft_constants.cache_info().misses == misses


@pytest.mark.parametrize("r,c", [(96, 40), (100, 36), (7, 5)])
def test_transpose_ragged_shapes_stay_exact(r, c):
    """Ragged scenes go through the padded Pallas tile path (no XLA
    fallback) and still transpose exactly."""
    from repro.kernels.transpose import transpose
    rng = np.random.default_rng(5)
    x = rng.standard_normal((r, c)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(transpose(jnp.asarray(x), tile=32)), x.T)
    xb = rng.standard_normal((2, r, c)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(transpose(jnp.asarray(xb), tile=32)),
        np.swapaxes(xb, -1, -2))


def test_bench_schema_interpret_flag():
    """Rows may carry an optional `interpret` bool; anything else fails
    validation (the ratchet relies on the flag to avoid diffing emulator
    wall time against compiled wall time)."""
    from benchmarks.common import BENCH_SCHEMA, utc_now_iso, \
        validate_bench_doc
    doc = {
        "schema": BENCH_SCHEMA, "git_sha": "x", "backend": "cpu",
        "jax_version": "0", "python": "3", "generated_utc": utc_now_iso(),
        "rows": [{"section": "s", "name": "rda_fused1", "wall_ms": 1.0,
                  "interpret": True}],
    }
    validate_bench_doc(doc)
    doc["rows"][0]["interpret"] = "yes"
    with pytest.raises(ValueError, match="interpret"):
        validate_bench_doc(doc)


def test_bench_ratchet_detects_regression_and_respects_flags():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "bench_compare_script",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def doc(ms, interpret=True, ref_ms=None, name="rda_fused1"):
        rows = [{"section": "t", "name": name, "wall_ms": ms,
                 "interpret": interpret}]
        if ref_ms is not None:
            rows.append({"section": "t", "name": "rda_unfused",
                         "wall_ms": ref_ms, "interpret": False})
        return {"rows": rows}

    pat = r"rda_(?!un).*fused"
    ok = mod.compare(doc(100.0), doc(110.0), pat, 1.3, 1.0)
    assert ok == []
    bad = mod.compare(doc(100.0), doc(150.0), pat, 1.3, 1.0)
    assert len(bad) == 1 and "1.50x" in bad[0]
    # interpret-flag mismatch is skipped, never a failure
    mixed = mod.compare(doc(100.0, interpret=False), doc(150.0), pat,
                        1.3, 1.0)
    assert mixed == []
    # the default pattern never gates the informational unfused oracle
    unfused = mod.compare(doc(1.0, name="rda_unfused", interpret=False),
                          doc(100.0, name="rda_unfused", interpret=False),
                          pat, 1.3, 0.0)
    assert unfused == []
    # reference-row normalization: a uniformly 2x slower machine (both
    # the fused row AND the reference doubled) does not trip the ratchet
    norm = mod.compare(doc(100.0, ref_ms=10.0), doc(200.0, ref_ms=20.0),
                       pat, 1.3, 1.0)
    assert norm == []
    # ...but a real fused-only regression still does
    real = mod.compare(doc(100.0, ref_ms=10.0), doc(200.0, ref_ms=10.0),
                       pat, 1.3, 1.0)
    assert len(real) == 1
