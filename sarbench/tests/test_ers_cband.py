"""The ERS-1/2 image-mode block (``configs/ers_cband_8192.json``) and its
cell, and the reader of the range step's roofline share, on hand-made
events and on one call recorded on a v5e."""
import json
import pathlib
import types

import pytest

from sarbench import scene as S, scopes, spec, trace as T, work

DATA = pathlib.Path(__file__).resolve().parent / "data"
CHIP = "/device:TPU:0"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "ers_cband_8192.batch1"
RANGE_OP = ("jit(focus_fused3)/range_comp_rcmc/dft6144_48x128/"
            "jit(spectral_op)/spectral_axis1/pallas_call")


def test_the_configuration_loads_and_validates():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1
    assert (cell.traffic["loop"], cell.traffic["batch"],
            cell.traffic["echoes"]) == ("closed", 1, 2)
    sc = S.scene_from_config(cell.config)
    sc.validate()
    assert (sc.na, sc.nr) == (8192, 6144)
    assert sc.pulse_samples == 704 and sc.aperture_samples == 1142
    assert 1400 < sc.doppler_bandwidth < sc.prf
    assert cell.config["variant"] == "fused3"
    assert cell.config["precision"] == "f32"
    assert 0 < cell.config["check"]["l2_rel_limit"] < 1e-5
    assert [m.name for m in cell.end_to_end] == ["scenes_per_s", "setup_s"]
    assert {m.name for m, _ in cell.per_layer} == {
        "device_idle_share.batch", "focus_roofline", "transfer_share.batch",
        "complex_glue_share.batch", "transfer_relayout_share.batch",
        "range_step_roofline.batch"}


def test_the_program_runs_the_range_length_unpadded():
    from repro.core.sar import build_pipeline
    from sarbench import harness

    config = spec.load_cell(CELL).config
    pipe = build_pipeline(harness.program_scene(config), config["variant"],
                          precision=config["precision"])
    assert pipe.spectral_dispatches() == [
        ("azimuth_fft", 0, 8192, (128, 64)),
        ("range_comp_rcmc", 1, 6144, (48, 128)),
        ("azimuth_compression", 0, 8192, (128, 64))]


def run_of(named, scenes, na=8192, nr=6144, window=(0, 10**9)):
    ops = {c: [("op", s, e) for _, s, e in evs] for c, evs in named.items()}
    t = scopes.ScopedTrace(ops, [], *window, named=named)
    return types.SimpleNamespace(
        trace=t, window=types.SimpleNamespace(scenes=scenes),
        scene=types.SimpleNamespace(na=na, nr=nr), peak=PEAK)


READ = spec.load_reader("range_step_roofline.batch")


def test_range_step_roofline_by_hand():
    # two scenes; the range kernel 20 ms and its glue 4 ms under the
    # step's scope, overlapping 1 ms; the azimuth steps do not count
    named = {CHIP: [
        ("jit(focus_fused3)/azimuth_fft/jit(spectral_op)", 0, 5_000_000),
        (RANGE_OP, 5_000_000, 25_000_000),
        ("jit(focus_fused3)/range_comp_rcmc/unsplit/add", 24_000_000,
         28_000_000),
        ("jit(focus_fused3)/azimuth_compression/jit(spectral_op)",
         28_000_000, 40_000_000)]}
    bytes_bound = 16 * 8192 * 6144 / 819e9
    ops = (2 * 8192 * 5 * 6144 * 12.584962500721156 + 12 * 8192 * 6144)
    assert bytes_bound > ops / 197e12
    want = 100 * 2 * bytes_bound / 23e-3
    assert READ(run_of(named, 2)) == pytest.approx(want, rel=1e-9)


def test_range_step_roofline_is_silent_without_step_names():
    named = {CHIP: [("jit(f)/spectral_axis1", 0, 5_000)]}
    assert READ(run_of(named, 2)) is None
    no_range = {CHIP: [("jit(focus_fused3)/azimuth_fft/x", 0, 5_000)]}
    assert READ(run_of(no_range, 2)) is None
    assert READ(run_of({CHIP: [(RANGE_OP, 0, 5_000)]}, 0)) is None
    assert READ(types.SimpleNamespace(trace=None)) is None


def test_range_step_roofline_on_a_recorded_v5e_call():
    """One call of four 4096² scenes recorded on a v5e: the range step's
    kernel and fusion take about 16 ms, 8 % of their 1.3 ms bytes bound."""
    doc = json.loads((DATA / "tpu_v5e_batch4_scoped_call.json").read_text())
    named = {CHIP: [(o, s, e) for _, o, s, e in doc["ops"]]}
    run = run_of(named, 4, 4096, 4096, doc["window"])
    seconds = run.trace.seconds_by_step()["range_comp_rcmc"]
    least = 16 * 4096 * 4096 / 819e9
    assert READ(run) == pytest.approx(100 * 4 * least / seconds)
    assert 5 < READ(run) < 12
    assert work.least_seconds(4096, 4096, PEAK)[0] == pytest.approx(least)
