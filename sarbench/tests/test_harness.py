"""The harness end to end on the CPU at a tiny size: cells found from
files alone, sound runs correct, broken runs and the control not."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from sarbench import harness, readings, spec

HERE = pathlib.Path(__file__).resolve().parent
SARBENCH = HERE.parent
ROOT = SARBENCH.parent
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

TINY = {
    "source": "a 128 x 128 reduction of the paper's scene for CPU tests",
    "scene": {"na": 128, "nr": 128, "fc": 10.0e9, "bandwidth": 100.0e6,
              "tp": 32 / 120.0e6, "fs": 120.0e6, "prf": 400.0, "v": 100.0,
              "r0": 5000.0, "aperture_time": 80 / 400.0, "noise_db": 20.0},
    "targets": [[0.0, 0.0, 1.0], [0.125, 0.0, 1.0], [0.0, 0.125, 1.0],
                [-0.125, -0.125, 1.0], [0.25, 0.1875, 1.0]],
    "variant": "fused3",
    "precision": "f32",
    # f32 on the CPU reads about 3e-7 here, the bf16x3 control about 1e-5
    "check": {"l2_rel_limit": 3e-6},
}
CLOSED = {"loop": "closed", "batch": 2, "echoes": 4}
NEW_READER = '''"""Added as a file only: the window's length."""


def read(run):
    return run.window.seconds
'''


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    """A benchmark of the real readers plus a config, a mix and a metric
    reader that exist only as new files."""
    base = tmp_path_factory.mktemp("bench") / "sarbench"
    shutil.copytree(SARBENCH / "metrics", base / "metrics")
    (base / "configs").mkdir()
    (base / "traffic").mkdir()
    (base / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (base / "traffic" / "closed2.json").write_text(json.dumps(CLOSED))
    (base / "metrics" / "window_length.py").write_text(NEW_READER)
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = dict(real)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "sarbench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.closed2", "config": "tiny", "traffic": "closed2",
         "chips": 1, "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = ["tiny.closed2"]
    bench["per_layer"].append(
        {"name": "window_length", "unit": "s", "better": "lower",
         "source": "host_clock", "layer": "test", "moves": "scenes_per_s",
         "workloads": ["tiny.closed2"]})
    path = base.parent / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path, base


@pytest.fixture(autouse=True)
def tune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))


def run(layout, name, trace=False, factory=None, seed=2**31 + 7,
        seconds=1.0):
    import jax

    path, base = layout
    cell = spec.load_cell(name, path, base)
    return harness.run_cell(cell, seed, seconds, trace, tag="[test]",
                            t_process=time.perf_counter(),
                            devices=jax.devices(), peak=PEAK,
                            factory=factory)


def test_new_files_alone_make_a_cell(layout):
    path, base = layout
    cell = spec.load_cell("tiny.closed2", path, base)
    assert cell.config["scene"]["na"] == 128 and cell.traffic["batch"] == 2
    assert [m.name for m in cell.end_to_end] == ["scenes_per_s", "setup_s"]
    names = [m.name for m, _ in cell.per_layer]
    assert "window_length" in names
    out = run(layout, "tiny.closed2", trace=True)
    # the traced window ends after TRACED_CALLS calls or its seconds
    assert out["metrics"]["window_length"]["value"] == \
        out["diagnostics"]["window_s"] > 0.0
    assert out["metrics"]["transfer_share.batch"]["unit"] == "%"


def test_closed_loop_sound_run(layout):
    out = run(layout, "tiny.closed2")
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == {"scenes_per_s", "setup_s"}
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["device"]["count"] >= 1
    assert list(out)[-1] == "compared"
    assert out["compared"]["l2_rel"]["value"] < 1e-6


# -- the traced window's cap ---------------------------------------------------

def _reference(config):
    """A fast and correct entry: the plain reference."""
    from sarbench import reference, scene

    return reference.Reference(scene.scene_from_config(config))


def test_a_traced_window_stops_at_its_cap(layout, monkeypatch):
    monkeypatch.setattr(harness, "TRACED_CALLS", 3)
    out = run(layout, "tiny.closed2", trace=True, factory=_reference,
              seconds=120.0)
    d = out["diagnostics"]
    assert d["traced_calls"] == 3 and d["window_s"] < 120.0
    assert out["attempted"] == 3 * CLOSED["batch"]
    assert out["correct"] is True, out["compared"]
    assert d["host_rss_peak_bytes"] > 0


def test_an_untraced_window_runs_past_the_cap(layout, monkeypatch):
    monkeypatch.setattr(harness, "TRACED_CALLS", 3)
    out = run(layout, "tiny.closed2", factory=_reference, seconds=2.0)
    d = out["diagnostics"]
    assert out["attempted"] > 3 * CLOSED["batch"]
    assert d["window_s"] >= 2.0 and "traced_calls" not in d
    assert d["host_rss_peak_bytes"] > 0
    assert out["correct"] is True, out["compared"]


# -- what must come out not correct -----------------------------------------

def _altered(config):
    f = harness.pipeline_entry(config)
    return lambda x: f(x).at[0].multiply(1.0 + 1e-3)


def _half_batch(config):
    import jax.numpy as jnp

    f = harness.pipeline_entry(config)

    def g(x):
        y = f(x[: x.shape[0] // 2])
        return jnp.concatenate([y, y])
    return g


def _stage_skipped(config):
    import jax

    from repro.core.sar import build_pipeline

    pipe = build_pipeline(harness.program_scene(config), config["variant"],
                          precision=config["precision"])
    pipe.steps = pipe.steps[:-1]          # azimuth compression returns
    return jax.jit(pipe.run)              # its input unchanged


@pytest.mark.parametrize("factory", [_altered, _half_batch, _stage_skipped,
                                     readings.control],
                         ids=["answer_altered", "half_batch_left_out",
                              "stage_returns_its_input", "control_bf16x3"])
def test_closed_loop_broken_path_is_not_correct(layout, factory):
    out = run(layout, "tiny.closed2", factory=factory)
    assert out["correct"] is False
    c = out["compared"]["l2_rel"]
    assert c["value"] > c["limit"]


def test_no_accelerator_means_no_result(tmp_path):
    """On the CPU the command exits non-zero and prints nothing on stdout;
    so does a checkout holding only the benchmark's own files."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for root in (ROOT, tmp_path):
        if root == tmp_path:
            shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
            shutil.copytree(SARBENCH, tmp_path / "sarbench",
                            ignore=shutil.ignore_patterns("__pycache__"))
        p = subprocess.run(
            [sys.executable, "sarbench/run.py", "--workload",
             "xband_airborne_4096.batch4", "--seed", "1", "--seconds", "1",
             "--trace", "0"], cwd=root, env=env, capture_output=True,
            text=True, timeout=300)
        assert p.returncode != 0
        assert p.stdout.strip() == ""
