"""repro.tuning: layering (src never imports benchmarks), batch-bucket
key normalization, KernelConfig plumbing, factorizations invariants, the
roofline cost model, the versioned cache + legacy migration, the guided
search policy, and the one-config-path bit-identity guarantees."""
import ast
import json
import math
import os

import numpy as np
import pytest
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - see requirements-dev.txt
    from _hypothesis_fallback import given, settings, strategies as st

from repro import tuning
from repro.tuning import cost
from repro.kernels import ops, ref
from repro.kernels.fft4step import (
    MAX_FACTOR,
    SpectralSpec,
    build_spectral_call,
    default_factorization,
)

SRC_ROOT = os.path.join(os.path.dirname(__file__), "..", "src", "repro")


# ---------------------------------------------------------------------------
# Layering: src/repro must not import benchmarks (the old inversion)
# ---------------------------------------------------------------------------

def test_src_never_imports_benchmarks():
    """core/plan.py used to reach *up* into benchmarks.autotune at compile
    time and service.py into benchmarks.bench_quality at admission; both
    now resolve through repro.tuning. Enforce it for the whole tree."""
    offenders = []
    for dirpath, _, files in os.walk(SRC_ROOT):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    names = [node.module or ""]
                else:
                    continue
                for name in names:
                    if name == "benchmarks" or \
                            name.startswith("benchmarks."):
                        offenders.append(f"{path}:{node.lineno}")
    assert not offenders, f"src/repro imports benchmarks: {offenders}"


# ---------------------------------------------------------------------------
# Keys: batch bucketing + device fingerprint
# ---------------------------------------------------------------------------

def test_batch_buckets_are_service_buckets():
    from repro.service import backends
    for b in (1, 2, 3, 4, 5, 7, 8, 9):
        assert tuning.bucket_batch(b) == backends._bucket(b)
    assert [tuning.bucket_batch(b) for b in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 16]


def test_tune_key_normalizes_batch_and_requires_buckets():
    k3 = tuning.TuneKey.kernel(512, 3)
    k4 = tuning.TuneKey.kernel(512, 4)
    assert k3 == k4 and k3.batch == 4
    with pytest.raises(ValueError, match="bucket"):
        tuning.TuneKey(kind="kernel", backend="cpu", device="cpu",
                       n=512, batch=3, lines=16)


def test_padded_batch_hits_exact_batch_cache_entry(tmp_path):
    """The satellite fix: the batcher pads B=3 to the B=4 bucket, so a
    config tuned at B=4 must be what a B=3 lookup resolves to."""
    cache = tuning.TuneCache(str(tmp_path / "c.json"))
    cfg = tuning.KernelConfig(block=16, n1=32, n2=16)
    cache.put(tuning.TuneKey.kernel(512, 4), cfg)
    assert tuning.cached_config(512, 3, cache=cache) == cfg
    assert tuning.cached_config(512, 4, cache=cache) == cfg
    assert tuning.cached_config(512, 5, cache=cache) is None  # bucket 8


def test_tune_key_encode_decode_roundtrip():
    for key in (tuning.TuneKey.kernel(4096, 3),
                tuning.TuneKey.pipeline("fused3", 256, 512, batch=2,
                                        precision="bs16")):
        assert tuning.TuneKey.decode(key.encode()) == key


def test_device_fingerprint_is_part_of_the_key(tmp_path):
    """'Beating vDSP': the winning decomposition is device-specific — a
    config tuned on another device kind must be invisible here."""
    cache = tuning.TuneCache(str(tmp_path / "c.json"))
    other = tuning.TuneKey.kernel(512, 1, device="TPU-v99")
    cache.put(other, tuning.KernelConfig(block=4))
    assert tuning.cached_config(512, 1, cache=cache) is None
    here = tuning.TuneKey.kernel(512, 1)
    cache.put(here, tuning.KernelConfig(block=4))
    assert tuning.cached_config(512, 1, cache=cache) is not None


# ---------------------------------------------------------------------------
# KernelConfig: the one config record
# ---------------------------------------------------------------------------

def test_kernel_config_spectral_kwargs_drop_deferred_knobs():
    c = tuning.KernelConfig(block=8, n1=64, n2=8, karatsuba=True)
    assert c.spectral_kwargs() == {"block": 8, "n1": 64, "n2": 8,
                                   "karatsuba": True}
    # col_block is pipeline-level: kernels must never see it
    assert "col_block" not in tuning.KernelConfig(
        col_block=256).spectral_kwargs()
    # an all-deferred config defers everything — karatsuba included
    # (tri-state), so a partial config never scrubs a pinned spec knob
    assert tuning.KernelConfig().spectral_kwargs() == {}


def test_kernel_config_from_dict_tolerates_legacy_extras():
    legacy = {"block": 16, "n1": 32, "n2": 16, "n3": None,
              "karatsuba": False, "precision": None, "seconds": 0.01}
    c = tuning.KernelConfig.from_dict(legacy)
    assert (c.block, c.factors()) == (16, (32, 16))
    # any radix up to the MXU edge is a factor (6144 = 48 * 128); past it
    # no matmul stage can run one
    assert tuning.KernelConfig(n1=96, n2=64).factors() == (96, 64)
    with pytest.raises(ValueError, match="between 1 and 128"):
        tuning.KernelConfig(n1=MAX_FACTOR + 1)
    with pytest.raises(ValueError, match="between 1 and 128"):
        tuning.SegmentConfig(n1=256)
    with pytest.raises(ValueError, match="precision"):
        tuning.KernelConfig(precision="f8")


def test_merge_overrides_replaces_factorization_wholesale():
    tuned = tuning.KernelConfig(block=8, n1=64, n2=8, n3=None,
                                precision="bf16")
    m = tuned.merge_overrides({"n1": 16, "n2": 32})
    assert m.factors() == (16, 32) and m.n3 is None
    assert m.precision == "bf16" and m.block == 8
    m2 = tuned.merge_overrides({"block": 4, "karatsuba": True})
    assert m2.factors() == (64, 8) and m2.block == 4 and m2.karatsuba


def test_build_spectral_call_accepts_kernel_config():
    """The kernels layer consumes a KernelConfig directly (duck-typed):
    same call as spelling the spec out by hand, bit for bit."""
    n = 256
    rng = np.random.default_rng(0)
    xr = jnp.asarray(rng.standard_normal((1, 8, n)), jnp.float32)
    xi = jnp.asarray(rng.standard_normal((1, 8, n)), jnp.float32)
    cfg = tuning.KernelConfig(block=4, n1=64, n2=4, karatsuba=True)
    base = SpectralSpec(n=n, fwd=True, inv=False, filter_mode="none")
    got = build_spectral_call(base, 8, batch=1, interpret=True,
                              config=cfg)(xr, xi)
    # a partial config must not scrub knobs the spec pins (tri-state
    # karatsuba): block-only config on a karatsuba spec keeps karatsuba
    pinned = SpectralSpec(n=n, fwd=True, inv=False, filter_mode="none",
                          karatsuba=True)
    applied = tuning.KernelConfig(block=4).apply(pinned)
    assert applied.karatsuba and applied.block == 4
    explicit = SpectralSpec(n=n, fwd=True, inv=False, filter_mode="none",
                            block=4, n1=64, n2=4, karatsuba=True)
    want = build_spectral_call(explicit, 8, batch=1, interpret=True)(xr, xi)
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    wantr = ref.fft_ref(np.asarray(xr[0]), np.asarray(xi[0]), axis=1)
    np.testing.assert_allclose(np.asarray(got[0][0]), wantr[0],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# factorizations(): the satellite invariants
# ---------------------------------------------------------------------------

def test_factorizations_invariants_up_to_2_21():
    n = 2
    while n <= 2 ** 21:
        fs = tuning.factorizations(n)
        assert fs, f"empty candidate set for n={n}"
        for f in fs:
            assert list(f) == sorted(f, reverse=True), (n, f)
            assert all(x <= MAX_FACTOR for x in f), (n, f)
            assert math.prod(f) == n, (n, f)
        kick_in = n > MAX_FACTOR * MAX_FACTOR
        assert all((len(f) == 3) == kick_in for f in fs), \
            f"3-factor must kick in exactly past 128*128 (n={n}: {fs})"
        n *= 2


def test_factorizations_rejects_non_power_of_two():
    """Non-powers of two are split like any length, led by the library
    default; a length with no split into factors <= 128 raises and is
    named, never padded to a longer transform."""
    fs = tuning.factorizations(6144)
    assert fs[0] == default_factorization(6144) == (48, 128)
    assert set(fs) == {(48, 128), (128, 48), (96, 64)}
    assert all(math.prod(f) == 96 and max(f) <= MAX_FACTOR
               for f in tuning.factorizations(96))
    for n in (2 * 131, 3 * MAX_FACTOR ** 3):
        with pytest.raises(ValueError, match=str(n)):
            tuning.factorizations(n)


# ---------------------------------------------------------------------------
# Cost model: ranking quality + feasibility never empties the space
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,batch", [(512, 1), (4096, 1), (4096, 4)])
def test_cost_model_ranks_known_best_in_top3(n, batch):
    """The paper's known-good shape — the ~sqrt factorization (4096 =
    64*64) — must appear in the model's top-3 for the reference points,
    else the guided search would skip the winner the exhaustive sweep
    finds (acceptance: same winner, strictly fewer timed)."""
    key = tuning.TuneKey.kernel(n, batch)
    ranked = cost.rank(tuning.candidates(n), key)
    top3 = [c.factors() for c in ranked[:3]]
    assert default_factorization(n) in top3, (top3, default_factorization(n))


def test_feasibility_cut_never_excludes_every_candidate():
    """Even when the VMEM budget rejects every candidate (a 2^20-point
    line slab cannot fit any block in 16 MiB) the ranking must fall back
    to structural feasibility rather than emptying the search space."""
    n = 256
    while n <= 2 ** 21:
        key = tuning.TuneKey.kernel(n, 1)
        assert cost.rank(tuning.candidates(n), key), \
            f"feasibility cut emptied n={n}"
        n *= 4
    # and the strict cut does cut: a huge batch-block slab is over budget
    big = tuning.TuneKey.kernel(2 ** 20, 16, lines=128)
    cands = tuning.candidates(2 ** 20, blocks=(128,))
    assert any(not cost.feasible(c, big) for c in cands)
    assert cost.rank(cands, big)      # ...yet the ranking still ranks


def test_cost_model_is_finite_positive_and_orders_precisions():
    key = tuning.TuneKey.kernel(4096, 4)
    f32 = tuning.KernelConfig(block=8, n1=64, n2=64, precision="f32")
    bf16 = tuning.KernelConfig(block=8, n1=64, n2=64, precision="bf16")
    t32 = cost.predicted_seconds(f32, key)
    t16 = cost.predicted_seconds(bf16, key)
    assert 0 < t16 <= t32 < 1.0
    assert cost.nominal_flops(key) > 0


# ---------------------------------------------------------------------------
# Cache: schema, migration, validation
# ---------------------------------------------------------------------------

def test_cache_migrates_legacy_flat_format(tmp_path):
    """A pre-subsystem cache file (flat exact-batch keys) must be read
    transparently: entries land under bucketed, device-stamped keys
    (fastest wins a bucket collision) and the next put() rewrites the
    file in schema 1."""
    path = str(tmp_path / "autotune_cache.json")
    legacy = {
        "cpu_B3_n512": {"block": 8, "n1": 32, "n2": 16, "n3": None,
                        "karatsuba": False, "precision": None,
                        "seconds": 0.010},
        "cpu_B4_n512": {"block": 16, "n1": 64, "n2": 8, "n3": None,
                        "karatsuba": True, "precision": None,
                        "seconds": 0.005},
        "cpu_B1_n4096": {"block": 4, "n1": 64, "n2": 64, "n3": None,
                         "karatsuba": False, "precision": "bf16",
                         "seconds": 0.020},
        "garbage": "not-a-config",
    }
    with open(path, "w") as f:
        json.dump(legacy, f)
    cache = tuning.TuneCache(path)
    # B3 and B4 collide in the B=4 bucket; the faster (B4) entry wins
    hit = cache.get(tuning.TuneKey.kernel(512, 3, backend="cpu"))
    assert hit is not None and hit.factors() == (64, 8) and hit.karatsuba
    hit2 = cache.get(tuning.TuneKey.kernel(4096, 1, backend="cpu"))
    assert hit2 is not None and hit2.precision == "bf16"
    # a put rewrites the file as a validated schema-1 document
    cache.put(tuning.TuneKey.kernel(256, 1), tuning.KernelConfig(block=8))
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"] == tuning.CACHE_SCHEMA
    tuning.validate_cache_doc(doc)
    assert len(doc["entries"]) == 3          # garbage dropped, B3/B4 merged


def test_cache_validation_rejects_malformed_docs():
    ok = {"schema": 1, "entries": {
        tuning.TuneKey.kernel(512, 1).encode(): {
            "config": {"block": 8}, "seconds": 0.1}}}
    tuning.validate_cache_doc(ok)
    with pytest.raises(ValueError, match="schema"):
        tuning.validate_cache_doc({"schema": 99, "entries": {}})
    with pytest.raises(ValueError, match="entries"):
        tuning.validate_cache_doc({"schema": 1})
    with pytest.raises(ValueError, match="TuneKey|malformed"):
        tuning.validate_cache_doc(
            {"schema": 1, "entries": {"bad key": {"config": {}}}})
    with pytest.raises(ValueError, match="config"):
        tuning.validate_cache_doc(
            {"schema": 1,
             "entries": {tuning.TuneKey.kernel(8, 1).encode(): {}}})


def test_cache_in_process_layer_rereads_on_file_change(tmp_path):
    path = str(tmp_path / "c.json")
    a = tuning.TuneCache(path)
    key = tuning.TuneKey.kernel(512, 1)
    assert a.get(key) is None
    b = tuning.TuneCache(path)               # independent view, same file
    b.put(key, tuning.KernelConfig(block=16))
    got = a.get(key)                         # a must observe b's write
    assert got is not None and got.block == 16


def test_cache_quarantines_truncated_json_and_rebuilds(tmp_path, caplog):
    """Corruption recovery: a truncated (mid-token) cache file must not
    poison every subsequent load — the unreadable bytes are quarantined
    to <path>.corrupt for post-mortem, the corruption is logged ONCE,
    and the cache rebuilds empty so puts/gets work again immediately."""
    import logging
    path = str(tmp_path / "c.json")
    cache = tuning.TuneCache(path)
    key = tuning.TuneKey.kernel(512, 1)
    cache.put(key, tuning.KernelConfig(block=16))
    with open(path, "r+b") as f:             # truncate mid-token
        f.truncate(17)
    cache._mtime = None                      # drop the in-process layer
    with caplog.at_level(logging.WARNING, logger="repro.tuning.cache"):
        assert cache.get(key) is None, "corrupt file reads as empty"
        assert cache.get(key) is None
    assert os.path.exists(path + ".corrupt"), \
        "the corrupt bytes are preserved for post-mortem"
    assert not os.path.exists(path)
    warned = [r for r in caplog.records if "quarantined" in r.getMessage()]
    assert len(warned) == 1, "corruption is logged once, not per load"
    # the cache is live again: a fresh put persists and round-trips
    cache.put(key, tuning.KernelConfig(block=32))
    assert tuning.TuneCache(path).get(key).block == 32


def test_cache_quarantines_wrong_shape_json(tmp_path):
    """Well-formed JSON of a foreign shape (a list, say) is corruption
    too: quarantine and rebuild rather than raising on every load."""
    path = str(tmp_path / "c.json")
    with open(path, "w") as f:
        json.dump([1, 2, 3], f)
    cache = tuning.TuneCache(path)
    assert cache.get(tuning.TuneKey.kernel(512, 1)) is None
    assert os.path.exists(path + ".corrupt")


# ---------------------------------------------------------------------------
# Guided search policy
# ---------------------------------------------------------------------------

def _fake_measure(times):
    calls = []

    def measure(cand, iters):
        calls.append(cand)
        return times[cand]

    return measure, calls


def test_search_times_strictly_fewer_candidates_and_finds_best(tmp_path):
    """With a deterministic oracle whose best config the cost model ranks
    in its top fraction, the guided search must return that best while
    timing strictly fewer distinct candidates than the space holds."""
    key = tuning.TuneKey.kernel(512, 1)
    space = tuning.candidates(512)
    ranked = cost.rank(space, key)
    best = ranked[1]                          # inside the measured half
    times = {c: (0.5 if c == best else 1.0 + i * 0.01)
             for i, c in enumerate(space)}
    measure, calls = _fake_measure(times)
    cache = tuning.TuneCache(str(tmp_path / "c.json"))
    res = tuning.search_kernel(key, measure=measure, cache=cache)
    assert res.config == best
    assert res.measured < len(space) and res.measured <= res.space
    assert res.predicted_rank == 1
    # the winner persisted: compile-time lookups now see it
    assert tuning.cached_config(512, 1, cache=cache) == best


def test_search_counts_candidates_that_failed_to_compile():
    """A candidate the compiler refuses is counted on the result, not
    lost: the winner comes from the rest."""
    key = tuning.TuneKey.kernel(512, 1)
    ranked = cost.rank(tuning.candidates(512), key)
    refused = ranked[0]

    def measure(cand, iters):
        if cand == refused:
            raise RuntimeError("Mosaic refused the kernel")
        return 1.0 + ranked.index(cand) * 0.01

    res = tuning.search_kernel(key, measure=measure, persist=False)
    assert res.dropped == 1 and (refused, None) in res.trace
    assert res.config == ranked[1]


def test_search_respects_snr_gate_without_timing_gated_configs():
    key = tuning.TuneKey.kernel(256, 1)
    space = tuning.candidates(256, precisions=("f32", "bs16"))
    times = {c: 1.0 for c in space}
    measure, calls = _fake_measure(times)
    gate_calls = []

    def gate(p):
        gate_calls.append(p)
        return 9.9                            # way out of gate

    res = tuning.search_kernel(key, precisions=("f32", "bs16"),
                               measure=measure, gate=gate, persist=False)
    assert gate_calls == ["bs16"]             # consulted once, not per cand
    assert all(c.precision == "f32" for c in calls)
    assert res.config.precision == "f32"


def test_measured_search_drops_raising_candidates(caplog):
    """A candidate whose measure raises is dropped, never silently: it
    is in the trace, at whatever rung it raised, and logged with its error."""
    def measure(cand, iters):
        if cand == "bad" or (cand == "late" and iters > 1):
            raise RuntimeError(f"infeasible at {iters} iters")
        return {"a": 3.0, "b": 1.0, "late": 0.5}[cand]

    with caplog.at_level("WARNING", logger="repro.tuning.search"):
        best, t, trace = tuning.measured_search(["bad", "a", "b", "late"],
                                                measure, rungs=(1, 3))
    assert best == "b" and t == 1.0
    assert ("bad", None) in trace and ("late", None) in trace
    assert sum(s is None for _, s in trace) == 2
    assert "infeasible at 1 iters" in caplog.text
    assert "infeasible at 3 iters" in caplog.text


def test_device_fingerprint_raises_without_a_device(monkeypatch):
    """No device is an error, never a cache key named 'unknown'."""
    import jax

    def no_devices():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="no backend"):
        tuning.space.device_fingerprint()


def test_device_table_rows():
    """The v5e row carries the published peaks; an unlisted kind raises."""
    v5e = cost.device_spec("TPU v5 lite")
    assert (v5e.peak_hbm_bytes, v5e.hbm_bytes) == (819e9, 16 * 10**9)
    assert v5e.peak_matmul_flops == 197e12 / 6
    assert v5e.line_block % 128 == 0 and not v5e.f16_operands
    assert "197 TFLOP/s" in v5e.source
    assert cost.device_spec().kind == "cpu"      # the test host
    with pytest.raises(KeyError, match="TPU v99"):
        cost.device_spec("TPU v99")


# ---------------------------------------------------------------------------
# The one config path: plans + service resolve through repro.tuning
# ---------------------------------------------------------------------------

def test_plan_compile_resolves_config_through_tuning(tmp_path, monkeypatch):
    """Seed the tuning cache with a distinctive config; a compiled plan's
    range dispatch must carry exactly those knobs, and the focused image
    must be bit-identical to compiling with the same config passed
    explicitly (the pre-refactor fft_kw path)."""
    import dataclasses

    from repro.core import plan as planlib
    from repro.core.sar import build_pipeline
    from repro.core.sar.geometry import test_scene

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()
    # rectangular on purpose: the cache entry is keyed n=nr=128, so the
    # azimuth (n=64) dispatches stay on defaults — mirroring fft_kw,
    # which configures range-axis dispatches only
    cfg = dataclasses.replace(test_scene(128), na=64)
    rng = np.random.default_rng(7)
    raw = jnp.asarray(rng.standard_normal((64, 128))
                      + 1j * rng.standard_normal((64, 128)), jnp.complex64)
    tuned = tuning.KernelConfig(block=4, n1=16, n2=8, karatsuba=True)
    tuning.get_cache().put(tuning.TuneKey.kernel(128, 1), tuned)

    pipe = build_pipeline(cfg, "fused3")
    row_steps = [s for s in pipe.steps
                 if s.kind == "spectral" and s.phys_axis == 1]
    assert row_steps, "fused3 must have a rows dispatch"
    for s in row_steps:
        kk = s.kernel_kw
        assert (kk["n1"], kk["n2"], kk["block"], kk["karatsuba"]) == \
            (16, 8, 4, True), kk

    img_tuned = np.asarray(pipe.run(raw))
    explicit = build_pipeline(cfg, "fused3", tune="off",
                              fft_kw=dict(block=4, n1=16, n2=8,
                                          karatsuba=True))
    assert np.array_equal(img_tuned, np.asarray(explicit.run(raw)))

    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()


def test_empty_cache_compiles_identically_to_tune_off(tmp_path,
                                                     monkeypatch):
    """A cache miss must leave the pipeline exactly on library defaults —
    bit-identical to tune='off' (the refactor cannot perturb outputs)."""
    from repro.core import plan as planlib
    from repro.core.sar import build_pipeline
    from repro.core.sar.geometry import test_scene

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()
    cfg = test_scene(128)
    rng = np.random.default_rng(11)
    raw = jnp.asarray(rng.standard_normal((128, 128))
                      + 1j * rng.standard_normal((128, 128)), jnp.complex64)
    a = np.asarray(build_pipeline(cfg, "fused3").run(raw))
    b = np.asarray(build_pipeline(cfg, "fused3", tune="off").run(raw))
    assert np.array_equal(a, b)
    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()


def test_service_warm_sweep_persists_and_is_reused(tmp_path, monkeypatch):
    """The serving warm sweep runs through tuning.measured_search and its
    winner lands in the shared cache under a pipeline-kind key, so a
    fresh backend (a restarted process) skips the sweep entirely."""
    from repro.core.sar.geometry import test_scene
    from repro.service import LocalBackend
    from repro.service.queue import BatchKey

    path = str(tmp_path / "c.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    tuning.clear_memory_cache()
    cfg = test_scene(128)
    bkey = BatchKey(cfg, "fused3", None, False)

    b1 = LocalBackend(sweep=((None, None), (32, -1)))
    b1.warm(bkey, max_batch=2)
    assert bkey in b1._best
    with open(path) as f:
        doc = json.load(f)
    tuning.validate_cache_doc(doc)
    pipe_entries = [k for k in doc["entries"]
                    if k.startswith(tuning.KIND_PIPELINE)]
    assert len(pipe_entries) == 1
    key = tuning.TuneKey.decode(pipe_entries[0])
    assert (key.variant, key.n, key.lines, key.batch) == ("fused3", 128,
                                                          128, 2)

    # a restarted process: same sweep config, but the cache pre-empts it
    def boom(*a, **k):
        raise AssertionError("swept despite a cache hit")

    monkeypatch.setattr(tuning, "measured_search", boom)
    b2 = LocalBackend(sweep=((None, None), (32, -1)))
    b2.warm(bkey, max_batch=2)
    assert b2._best[bkey] == b1._best[bkey]
    tuning.clear_memory_cache()


def test_shim_best_config_matches_subsystem(tmp_path, monkeypatch):
    """benchmarks/autotune.py is a thin shim: its dict API must resolve
    through the same cache the subsystem writes."""
    from benchmarks import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    tuning.clear_memory_cache()
    cfg = tuning.KernelConfig(block=16, n1=64, n2=8)
    tuning.get_cache().put(tuning.TuneKey.kernel(512, 2), cfg)
    d = autotune.best_config(512, 2, tune_missing=False)
    assert tuning.KernelConfig.from_dict(d) == cfg
    assert autotune.spectral_kwargs(d) == cfg.spectral_kwargs()
    # miss -> library defaults, never a sweep with tune_missing=False
    d2 = autotune.best_config(8192, 1, tune_missing=False)
    assert d2["n1"] is None and d2["block"] == 8
    tuning.clear_memory_cache()

# ---------------------------------------------------------------------------
# Property tests: key/config/schedule round-trips (hypothesis or fallback)
# ---------------------------------------------------------------------------

_PROP_NS = (64, 128, 256, 512, 1024)


@settings(max_examples=30, deadline=None)
@given(kind=st.sampled_from([tuning.KIND_KERNEL, tuning.KIND_PIPELINE]),
       n=st.sampled_from(_PROP_NS), bexp=st.integers(0, 6),
       lines=st.sampled_from([16, 64, 128]),
       precision=st.sampled_from([None, "f32", "bf16", "bs16"]),
       variant=st.sampled_from([None, "fused3", "csa_fused"]))
def test_prop_tune_key_encode_decode_roundtrip(kind, n, bexp, lines,
                                               precision, variant):
    key = tuning.TuneKey(kind=kind, backend="cpu", device="cpu", n=n,
                         batch=2 ** bexp, lines=lines,
                         precision=precision, variant=variant)
    assert tuning.TuneKey.decode(key.encode()) == key


@settings(max_examples=40, deadline=None)
@given(n=st.sampled_from(_PROP_NS), fi=st.integers(0, 10 ** 6),
       block=st.sampled_from([None, 4, 8, 16]),
       karatsuba=st.sampled_from([None, False, True]),
       precision=st.sampled_from([None, "f32", "bf16", "bs16"]),
       col_block=st.sampled_from([None, 128, 256]),
       residency=st.sampled_from([None, "vmem", "staged"]),
       phase_block=st.sampled_from([None, 8, 16]),
       buffer_depth=st.sampled_from([None, 1, 2, 3]))
def test_prop_kernel_config_dict_roundtrip(n, fi, block, karatsuba,
                                           precision, col_block, residency,
                                           phase_block, buffer_depth):
    """to_dict/from_dict must round-trip every knob — the tri-state
    karatsuba, the mega knobs incl. buffer_depth — including through the
    JSON wire format the cache stores."""
    fs = tuning.factorizations(n)
    f = (tuple(fs[fi % len(fs)]) + (None,))[:3]
    cfg = tuning.KernelConfig(block=block, n1=f[0], n2=f[1], n3=f[2],
                              karatsuba=karatsuba, precision=precision,
                              col_block=col_block, residency=residency,
                              phase_block=phase_block,
                              buffer_depth=buffer_depth)
    assert tuning.KernelConfig.from_dict(cfg.to_dict()) == cfg
    assert tuning.KernelConfig.from_dict(
        json.loads(json.dumps(cfg.to_dict()))) == cfg


@settings(max_examples=30, deadline=None)
@given(n=st.sampled_from(_PROP_NS), nseg=st.integers(1, 3),
       fi=st.integers(0, 10 ** 6),
       karatsuba=st.sampled_from([None, False, True]),
       residency=st.sampled_from([None, "vmem", "staged"]),
       buffer_depth=st.sampled_from([None, 1, 2]))
def test_prop_schedule_dict_roundtrip(n, nseg, fi, karatsuba, residency,
                                      buffer_depth):
    fs = tuning.factorizations(n)
    segs = tuple(
        tuning.SegmentConfig(*(tuple(fs[(fi + i) % len(fs)]) + (None,))[:3],
                             karatsuba=karatsuba)
        for i in range(nseg))
    s = tuning.Schedule(segments=segs, block=8, residency=residency,
                        buffer_depth=buffer_depth)
    assert tuning.Schedule.from_dict(s.to_dict()) == s
    assert tuning.Schedule.from_dict(
        json.loads(json.dumps(s.to_dict()))) == s


def test_kernel_config_is_degenerate_one_segment_schedule():
    cfg = tuning.KernelConfig(block=8, n1=32, n2=16, karatsuba=True,
                              residency="staged", phase_block=8,
                              buffer_depth=2)
    s = tuning.Schedule.from_config(cfg)
    assert s.uniform() and s.to_config() == cfg
    multi = tuning.Schedule(segments=(tuning.SegmentConfig(32, 16),
                                      tuning.SegmentConfig(16, 32)),
                            block=8)
    assert not multi.uniform()
    assert multi.to_config().n1 is None   # flat-inexpressible, by design


def test_timeit_enforces_repeat_floor():
    """A 1-iteration halving rung must still take TIMING_REPEATS_FLOOR
    timed samples so the median washes out scheduler jitter."""
    from repro.tuning import search as searchlib

    calls = []

    def fn():
        calls.append(1)
        return jnp.zeros(())

    searchlib._timeit(fn, warmup=1, iters=1)
    assert len(calls) == 1 + max(1, tuning.TIMING_REPEATS_FLOOR)
    calls.clear()
    searchlib._timeit(fn, warmup=0, iters=tuning.TIMING_REPEATS_FLOOR + 4)
    assert len(calls) == tuning.TIMING_REPEATS_FLOOR + 4


# ---------------------------------------------------------------------------
# The schedule graph: cache schema 2, migration, search, compiler, service
# ---------------------------------------------------------------------------

def test_cache_schema1_migrates_to_schema2_without_research(tmp_path,
                                                           monkeypatch):
    """A schema-1 file must resolve through the schema-2 cache with NO
    re-search: flat entries serve both get() and get_schedule() (as the
    degenerate one-segment schedule), their payload — the fastest-known
    measurement — passes through untouched, and the next put rewrites
    the file in schema 2 keeping the migrated entry."""
    key = tuning.TuneKey.kernel(512, 1)
    cfg = tuning.KernelConfig(block=16, n1=32, n2=16, karatsuba=True)
    path = str(tmp_path / "c.json")
    with open(path, "w") as f:
        json.dump({"schema": 1, "entries": {key.encode(): {
            "config": cfg.to_dict(), "seconds": 3.25e-4,
            "source": "search", "updated_utc": "2026-01-01T00:00:00Z"}}}, f)

    def boom(*a, **k):
        raise AssertionError("re-searched a migrated schema-1 entry")

    monkeypatch.setattr(tuning, "measured_search", boom)
    monkeypatch.setattr(tuning, "search_kernel", boom)
    cache = tuning.TuneCache(path)
    doc = cache.doc()
    assert doc["schema"] == tuning.CACHE_SCHEMA == 2
    assert cache.get(key) == cfg
    sched = cache.get_schedule(key)
    assert sched == tuning.Schedule.from_config(cfg)
    assert sched.to_config() == cfg
    assert cache.get_entry(key)["seconds"] == 3.25e-4

    cache.put(tuning.TuneKey.kernel(256, 1), tuning.KernelConfig(block=8))
    with open(path) as f:
        ondisk = json.load(f)
    assert ondisk["schema"] == 2
    assert ondisk["entries"][key.encode()]["config"] == cfg.to_dict()


def test_cache_schedule_roundtrip_and_flat_view(tmp_path):
    """put_schedule persists the Schedule AND its derived flat view, so
    schedule consumers round-trip exactly while flat-only consumers keep
    resolving the entry; the stored document schema-validates."""
    path = str(tmp_path / "c.json")
    key = tuning.TuneKey.kernel(256, 1)
    sched = tuning.Schedule(
        segments=(tuning.SegmentConfig(16, 16, None, True),
                  tuning.SegmentConfig(8, 32, None, False)),
        block=8, precision="f32", residency="staged", phase_block=8,
        buffer_depth=2)
    tuning.TuneCache(path).put_schedule(key, sched, seconds=1e-3)

    fresh = tuning.TuneCache(path)           # independent view, same file
    assert fresh.get_schedule(key) == sched
    flat = fresh.get(key)
    assert flat == sched.to_config()
    assert flat.n1 is None        # non-uniform: no flat factorization
    assert flat.residency == "staged" and flat.buffer_depth == 2
    tuning.validate_cache_doc(fresh.doc())


def test_graph_search_finds_flat_inexpressible_schedule():
    """The acceptance bar for the schedule graph: on a multi-segment
    megakernel problem whose axes differ, the search returns a schedule
    with DIFFERENT factorizations across segments (no flat KernelConfig
    can express it) whose predicted and measured cost match-or-beat the
    best flat-expressible schedule."""
    from repro.kernels.fft4step import default_factorization

    problem = tuning.ScheduleProblem.mega_2d(
        na=64, nr=256,
        segments=(tuning.SegmentShape(0, fwd=True),
                  tuning.SegmentShape(1, fwd=True, inv=True, filtered=True),
                  tuning.SegmentShape(0, inv=True, filtered=True)))

    def measure(s, iters):                 # deterministic oracle
        return cost.schedule_seconds(s, problem)

    res = tuning.search_schedule(problem, k=8, measure=measure,
                                 persist=False)
    win = res.schedule
    assert win is not None and len(win.segments) == 3
    assert not win.uniform()
    assert win.to_config().n1 is None      # the flat sweep can't say this

    # flat baseline: what compiling WITHOUT a schedule reaches — one
    # global candidates(nr) config (range segments take its split,
    # azimuth segments fall back to the default factorization), same
    # residency lane as the winner for a fair comparison
    def flat_schedule(c):
        segs = []
        for shp in problem.segments:
            if shp.axis == 1:
                segs.append(tuning.SegmentConfig(c.n1, c.n2, c.n3,
                                                 bool(c.karatsuba)))
            else:
                f = (tuple(default_factorization(problem.na)) + (None,))[:3]
                segs.append(tuning.SegmentConfig(*f, bool(c.karatsuba)))
        return tuning.Schedule(
            segments=tuple(segs), block=c.block, precision=c.precision,
            residency=win.residency, phase_block=win.phase_block,
            buffer_depth=win.buffer_depth)

    flats = [flat_schedule(c) for c in tuning.candidates(problem.nr)]
    flat_best = min(cost.schedule_seconds(s, problem) for s in flats)
    assert cost.schedule_seconds(win, problem) <= flat_best   # predicted
    assert res.seconds <= min(measure(s, 1) for s in flats)   # measured


def test_plan_compiles_through_schedule_to_kernel(tmp_path, monkeypatch):
    """compile_plan(schedule=...) routes per-segment factorization and
    karatsuba into the megakernel's extended segment records (and
    buffer_depth into the kernel kwargs), and the scheduled image stays
    allclose to the unscheduled pipeline."""
    from repro.core import plan as planlib
    from repro.core.sar import build_pipeline
    from repro.core.sar.geometry import test_scene

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()
    cfg = test_scene(128)
    sched = tuning.Schedule(
        segments=(tuning.SegmentConfig(16, 8, None, True),
                  tuning.SegmentConfig(8, 16, None, False),
                  tuning.SegmentConfig(8, 16, None, None)),
        residency="staged", phase_block=8, buffer_depth=3)
    pipe = build_pipeline(cfg, "fused1", schedule=sched)
    mega = [s for s in pipe.steps if s.kind == "mega"]
    assert len(mega) == 1
    kk = mega[0].kernel_kw
    assert kk["residency"] == "staged" and kk["buffer_depth"] == 3
    assert [rec[4:] for rec in kk["segments"]] == [
        (16, 8, None, True), (8, 16, None, False), (8, 16, None, None)]

    rng = np.random.default_rng(3)
    raw = jnp.asarray(rng.standard_normal((128, 128))
                      + 1j * rng.standard_normal((128, 128)), jnp.complex64)
    img = np.asarray(pipe.run(raw))
    ref_img = np.asarray(build_pipeline(cfg, "fused1", tune="off").run(raw))
    scale = max(1.0, float(np.abs(ref_img).max()))
    np.testing.assert_allclose(img, ref_img, atol=2e-4 * scale, rtol=0)
    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()


def test_service_warm_consumes_persisted_schedule(tmp_path, monkeypatch):
    """A graph-search Schedule persisted under the pipeline key must be
    picked up by the warm path and compiled into the served pipeline —
    its per-segment decisions reaching each dispatch in step order."""
    from repro.core import plan as planlib
    from repro.core.sar.geometry import test_scene
    from repro.service import LocalBackend
    from repro.service.queue import BatchKey

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()
    cfg = test_scene(128)
    bkey = BatchKey(cfg, "fused3", None, False)
    tkey = tuning.TuneKey.pipeline(variant="fused3", na=128, nr=128, batch=2)
    sched = tuning.Schedule(
        segments=(tuning.SegmentConfig(16, 8, None, True),
                  tuning.SegmentConfig(8, 16, None, False),
                  tuning.SegmentConfig(16, 8, None, True)),
        block=4, col_block=128)
    tuning.get_cache().put_schedule(tkey, sched, seconds=1e-3)

    b = LocalBackend(sweep=((None, None), (32, -1)), fused1="off")
    b.warm(bkey, max_batch=2)
    assert b._sched[bkey] == sched
    spect = [s for s in b._pipeline(bkey).steps if s.kind == "spectral"]
    assert [(s.kernel_kw["n1"], s.kernel_kw["n2"], s.kernel_kw["karatsuba"])
            for s in spect] == [(16, 8, True), (8, 16, False), (16, 8, True)]
    tuning.clear_memory_cache()
    planlib.clear_pipeline_cache()
