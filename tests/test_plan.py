"""SpectralPlan IR: serialization, fusion legality, backend equivalence,
streaming tiles, the ω-K plan, and the per-stage precision policy."""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import plan as planlib
from repro.core.plan import (
    SpectralPlan,
    Stage,
    plan_dispatch_count,
    plan_from_json,
    plan_to_json,
)
from repro.core.sar import (
    build_pipeline,
    documented_dispatches,
    metrics,
    paper_targets,
    simulate_cached,
    variant_names,
)
from repro.core.sar.geometry import test_scene as make_test_scene
from repro.kernels import ops, ref

CFG = make_test_scene(256)
TARGETS = paper_targets(CFG)

ALL_VARIANTS = ("unfused", "fused", "fused_tfree", "fused3",
                "csa", "csa_fused", "omegak")


def scene():
    return jnp.asarray(simulate_cached(CFG, TARGETS))


@pytest.fixture(scope="module")
def rda_reference():
    return np.asarray(build_pipeline(CFG, "unfused").run(scene()))


# ---------------------------------------------------------------------------
# IR round-trip + fusion legality
# ---------------------------------------------------------------------------

def test_all_variants_registered():
    assert set(ALL_VARIANTS) <= set(variant_names())


@pytest.mark.parametrize("variant", ALL_VARIANTS)
def test_plan_serialization_roundtrip(variant):
    var = planlib.get_variant(variant)
    plan = var.plan_fn()
    assert plan_from_json(plan_to_json(plan)) == plan
    # and with non-default plan parameters where the variant has them
    if "r_ref" in var.plan_kw:
        plan2 = var.plan_fn(r_ref=1234.5)
        assert plan_from_json(plan_to_json(plan2)) == plan2
        assert plan2.param_dict()["r_ref"] == 1234.5


@pytest.mark.parametrize("variant", ALL_VARIANTS)
def test_fusion_legality_dispatch_count(variant):
    """The fusion compiler must reproduce each variant's documented
    dispatch count exactly — no over- or under-fusion."""
    var = planlib.get_variant(variant)
    fuse = dict(var.compile_defaults).get("fuse", True)
    assert plan_dispatch_count(var.plan_fn(), fuse=fuse) == var.dispatches
    p = build_pipeline(CFG, variant)
    assert p.dispatches == documented_dispatches(variant) == var.dispatches


def test_fusion_grammar_barriers():
    """mul-after-ifft and fft-after-fft never fuse; transposes are walls."""
    two_ffts = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True),
        Stage("b", axis=1, fwd=True),
    ))
    assert plan_dispatch_count(two_ffts) == 2
    mul_after_inv = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True, inv=True, filters=("range_mf",)),
        Stage("b", axis=1, filters=("range_mf",)),
    ))
    assert plan_dispatch_count(mul_after_inv) == 2
    across_transpose = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True),
        Stage("t", kind="transpose"),
        Stage("b", axis=0, inv=True),
    ))
    assert plan_dispatch_count(across_transpose) == 3
    # the canonical fusion: fft + two muls + ifft on one axis is ONE dispatch
    fused3_mid = SpectralPlan("p", (
        Stage("a", axis=1, fwd=True, inv=True,
              filters=("range_mf", "rcmc_shift")),
    ))
    assert plan_dispatch_count(fused3_mid) == 1


# ---------------------------------------------------------------------------
# Executor equivalences
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["fused3", "csa_fused", "omegak"])
def test_pallas_matches_xla_backend(variant):
    """Interpret-mode equivalence: the same plan compiled to fused Pallas
    dispatches and to unfused jnp oracle ops agrees at FP32 roundoff."""
    a = np.asarray(build_pipeline(CFG, variant).run(scene()))
    b = np.asarray(build_pipeline(CFG, variant, backend="xla",
                                  fuse=False).run(scene()))
    assert metrics.l2_relative_error(a, b) < 1e-5


def test_unfused_fuses_to_four_dispatches():
    """One plan, two compilations: the textbook RDA plan fused collapses
    3+1+1+2 atoms to [rc][az_fft][sinc][az_comp]."""
    var = planlib.get_variant("unfused")
    assert plan_dispatch_count(var.plan_fn(), fuse=True) == 4
    img_fused = np.asarray(planlib.compile_plan(
        var.plan_fn(), CFG, fuse=True).run(scene()))
    img_ref = np.asarray(build_pipeline(CFG, "unfused").run(scene()))
    assert metrics.l2_relative_error(img_fused, img_ref) < 1e-5


# ---------------------------------------------------------------------------
# Streaming tiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["fused3", "omegak", "fused_tfree",
                                     "csa_fused"])
def test_streaming_bit_identical(variant):
    """The streaming executor over >=4 azimuth strips is bit-identical to
    the in-memory path (the kernel treats line blocks independently)."""
    p = build_pipeline(CFG, variant)
    raw = simulate_cached(CFG, TARGETS)
    mem = np.asarray(p.run(jnp.asarray(raw)))
    assert np.array_equal(p.run_streamed(raw, strips=4), mem)
    # ragged strip sizes must not change the numerics either
    assert np.array_equal(p.run_streamed(raw, strips=5), mem)


def test_streaming_rejects_transposed_plans():
    p = build_pipeline(CFG, "fused")   # the paper variant needs transposes
    with pytest.raises(ValueError, match="streaming"):
        p.run_streamed(simulate_cached(CFG, TARGETS), strips=4)


# ---------------------------------------------------------------------------
# The ω-K plan (tentpole acceptance)
# ---------------------------------------------------------------------------

def test_omegak_peaks_within_1px_of_rda(rda_reference):
    from repro.core.sar.rda import focus
    img = np.asarray(focus(scene(), CFG, variant="omegak"))
    ref_reps = metrics.analyze_scene(rda_reference, CFG, TARGETS)
    got_reps = metrics.analyze_scene(img, CFG, TARGETS)
    for tgt, r, g in zip(TARGETS, ref_reps, got_reps):
        assert abs(g.row - r.row) <= 1 and abs(g.col - r.col) <= 1, \
            (tgt, (g.row, g.col), (r.row, r.col))
        assert g.snr_db > 30.0, (tgt, g)


def test_omegak_batched_matches_unbatched():
    p = build_pipeline(CFG, "omegak")
    raw = scene()
    batch = jnp.stack([raw, 0.5 * raw])
    out = np.asarray(p.run(batch))
    one = np.asarray(p.run(raw))
    np.testing.assert_array_equal(out[0], one)
    scale = float(np.max(np.abs(one)))
    np.testing.assert_allclose(out[1], 0.5 * one, atol=1e-5 * scale, rtol=0)


# ---------------------------------------------------------------------------
# Precision policy
# ---------------------------------------------------------------------------

def test_bs16_block_scaling_rescues_f16_overflow():
    rng = np.random.default_rng(3)
    xr = rng.standard_normal((4, 512)).astype(np.float32) * 1e6
    xi = rng.standard_normal((4, 512)).astype(np.float32) * 1e6
    want = ref.fft_ref(xr, xi, axis=1)
    plain = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), precision="f16",
                         block=4)
    assert not np.isfinite(np.asarray(plain[0])).all()   # f16 overflows
    got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi), precision="bs16",
                       block=4)
    scale = float(jnp.max(jnp.abs(want[0])))
    assert np.isfinite(np.asarray(got[0])).all()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-3 * scale, rtol=0)


def test_bs16_beats_bf16_accuracy():
    """The point of block scaling: f16's 11-bit mantissa under a shared
    exponent is markedly more accurate than bf16's 8-bit mantissa."""
    rng = np.random.default_rng(4)
    xr = rng.standard_normal((8, 1024)).astype(np.float32)
    xi = rng.standard_normal((8, 1024)).astype(np.float32)
    want = ref.fft_ref(xr, xi, axis=1)

    def err(precision):
        got = ops.fft_rows(jnp.asarray(xr), jnp.asarray(xi),
                           precision=precision, block=4)
        return float(jnp.max(jnp.abs(got[0] - want[0])))

    assert err("bs16") < err("bf16") / 2


def test_stage_precision_threads_through_plan():
    """A per-stage precision override reaches the kernel: a bs16-stage
    pipeline differs from f32 but stays within narrow-float tolerance."""
    img32 = np.asarray(build_pipeline(CFG, "fused3", tune="off").run(scene()))
    img16 = np.asarray(build_pipeline(CFG, "fused3", tune="off",
                                      precision="bs16").run(scene()))
    assert not np.array_equal(img16, img32)
    c = metrics.compare_pipelines(img16, img32, CFG, TARGETS)
    assert max(c["snr_delta_db"]) < 0.3, c["snr_delta_db"]


def test_precision_gate_function():
    from benchmarks.bench_quality import precision_snr_deviation
    dev = precision_snr_deviation("bs16")
    assert 0.0 <= dev < 0.3


# ---------------------------------------------------------------------------
# Filter cache
# ---------------------------------------------------------------------------

def test_filter_cache_skips_host_math_on_recompile():
    cfg = dataclasses.replace(CFG, seed=999)   # a key no other test warms
    build_pipeline(cfg, "omegak")
    before = planlib.filter_cache_stats()
    build_pipeline(cfg, "omegak")              # a "new scene" with same cfg
    after = planlib.filter_cache_stats()
    assert after["misses"] == before["misses"]


def test_jitted_pipeline_names_its_module_steps_and_glue():
    """The jitted fused3 pipeline lowers to module ``jit_focus_fused3``,
    and every operation of its body carries its plan step's scope; inside
    each step, the complex <-> f32-plane conversions carry ``split`` and
    ``unsplit``. A device profile attributes operations by these names."""
    import re

    import jax

    pipe = build_pipeline(make_test_scene(128), "fused3")
    steps = [s.name for s in pipe.steps]
    assert steps == ["azimuth_fft", "range_comp_rcmc", "azimuth_compression"]
    x = jax.ShapeDtypeStruct((2, 128, 128), jnp.complex64)
    text = pipe.jitted().lower(x).as_text(debug_info=True)
    assert re.search(r"^module @jit_focus_fused3\b", text, re.M)
    names = set(re.findall(r'loc\("(jit\(focus_fused3\)/[^"]*)"', text))
    scopes = {n.split("/")[1] for n in names}
    assert scopes == set(steps)
    for step in steps:
        for glue in ("split", "unsplit"):
            assert any(n.startswith(f"jit(focus_fused3)/{step}/{glue}/")
                       for n in names), (step, glue)


def test_unknown_filter_and_variant_raise():
    bad = SpectralPlan("p", (Stage("a", axis=1, fwd=True,
                                   filters=("nope",)),))
    with pytest.raises(KeyError, match="nope"):
        planlib.compile_plan(bad, CFG)
    with pytest.raises(KeyError, match="variant"):
        build_pipeline(CFG, "not_a_variant")
