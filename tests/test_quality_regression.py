"""Golden quality regression: the five-point-target reference scene.

The fixture in ``tests/golden/point_targets_n128.json`` stores, for each
algorithm family (RDA / CSA / omega-K), the per-target peak location and
SNR of the f32 per-axis reference image. Every serving route must
reproduce it:

* f32 — at exactly 0.0 dB deviation (the routes are bit-identical, so
  the measured SNR equals the stored SNR to the last ulp), for fused3,
  fused1 VMEM-resident, fused1 DMA-staged, and (slow) the 8-device
  sharded lowering;
* bf16 / bs16 — within the 0.1 dB serving gate, same routes. The full
  precision matrix runs for RDA; CSA and omega-K check f32 + bs16 (the
  block-scaled tier is the serving default and the route most likely to
  regress — its exponents are carried through the kernels);
* raw f16 — asserted OUT of gate: the un-scaled half float overflows on
  FFT intermediates (NaN image), which is exactly why the serving tier
  is bs16 (f16 storage behind per-line block exponents), not f16.

Regenerate the fixture after an INTENDED quality change with::

    PYTHONPATH=src python tests/test_quality_regression.py --regen
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.sar import build_pipeline, metrics, paper_targets, \
    simulate_cached
from repro.core.sar.geometry import test_scene as make_test_scene

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "point_targets_n128.json")

N = 128
# tests/golden runs the 128^2 scene for speed; the default guard (64)
# would mask the whole image there, so the corpus pins a 16-px guard.
GUARD = 16

# family -> (per-axis variant, megakernel twin); mirrors
# repro.service.backends.FUSED1_TWINS
FAMILIES = {
    "rda": ("fused3", "fused1"),
    "csa": ("csa_fused", "csa_fused1"),
    "omegak": ("omegak", "omegak_fused1"),
}

GATE_DB = 0.1
PRECISIONS_FULL = (None, "bf16", "bs16")
PRECISIONS_TIER = (None, "bs16")

_scene_cache = {}


def scene():
    if "raw" not in _scene_cache:
        cfg = make_test_scene(N)
        _scene_cache["cfg"] = cfg
        _scene_cache["targets"] = paper_targets(cfg)
        _scene_cache["raw"] = np.asarray(
            simulate_cached(cfg, _scene_cache["targets"]), np.complex64)
    return (_scene_cache["cfg"], _scene_cache["targets"],
            _scene_cache["raw"])


def golden_reports(img, cfg, targets):
    """(row, col, snr_db) per target, with the corpus guard width."""
    noise = metrics.noise_rms(img, cfg, targets, guard=GUARD)
    out = []
    for t in targets:
        rep = metrics.analyze_target(img, cfg, t, noise)
        out.append({"row": rep.row, "col": rep.col, "snr_db": rep.snr_db})
    return out


def focus(variant, precision=None, residency=None):
    cfg, _targets, raw = scene()
    kw = {"tune": "off"}
    if precision is not None:
        kw["precision"] = precision
    if residency is not None:
        kw["residency"] = residency
    return np.asarray(build_pipeline(cfg, variant, **kw).run(
        jnp.asarray(raw)))


def load_golden():
    with open(GOLDEN_PATH) as f:
        doc = json.load(f)
    assert doc["scene_n"] == N and doc["guard"] == GUARD
    return doc


# route id -> (use twin?, residency kwarg)
ROUTES = {
    "fused3": (False, None),
    "fused1": (True, None),             # VMEM-resident megakernel
    "fused1_staged": (True, "staged"),  # DMA-staged megakernel
}


def _check(family, route, precision):
    golden = load_golden()["families"][family]
    cfg, targets, _raw = scene()
    per_axis, twin = FAMILIES[family]
    use_twin, residency = ROUTES[route]
    img = focus(twin if use_twin else per_axis, precision=precision,
                residency=residency)
    got = golden_reports(img, cfg, targets)
    for i, (g, m) in enumerate(zip(golden["targets"], got)):
        dev = abs(m["snr_db"] - g["snr_db"])
        if precision is None:
            # f32 routes are bit-identical: peak pixel AND SNR exact
            assert (m["row"], m["col"]) == (g["row"], g["col"]), \
                f"target {i}: f32 peak moved {g['row'], g['col']} -> " \
                f"{m['row'], m['col']} ({family}/{route})"
            assert dev == 0.0, \
                f"target {i}: f32 SNR deviated {dev} dB " \
                f"({family}/{route}) — the f32 route must be exact"
        else:
            # narrow precisions: quantization can tip a near-tied
            # mainlobe sample, so the peak may drift a pixel or two —
            # the gate is the SNR deviation, not the argmax
            assert (abs(m["row"] - g["row"]) <= 2
                    and abs(m["col"] - g["col"]) <= 2), \
                f"target {i}: {precision} peak moved " \
                f"{g['row'], g['col']} -> {m['row'], m['col']} " \
                f"({family}/{route})"
            assert dev <= GATE_DB, \
                f"target {i}: {precision} SNR deviation {dev:.4f} dB " \
                f"exceeds the {GATE_DB} dB gate ({family}/{route})"


@pytest.mark.parametrize("precision", PRECISIONS_FULL,
                         ids=[p or "f32" for p in PRECISIONS_FULL])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_rda_golden_quality(route, precision):
    _check("rda", route, precision)


@pytest.mark.parametrize("precision", PRECISIONS_TIER,
                         ids=[p or "f32" for p in PRECISIONS_TIER])
@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("family", ["csa", "omegak"])
def test_csa_omegak_golden_quality(family, route, precision):
    _check(family, route, precision)


def test_raw_f16_is_out_of_gate():
    """The negative control the bs16 tier exists for: UN-scaled f16
    overflows on FFT intermediates (its max finite value is 65504), so
    the raw-f16 image fails the golden corpus outright. If this ever
    starts passing, the scene stopped exercising the dynamic range that
    motivates block scaling — regenerate it with a harder one."""
    golden = load_golden()["families"]["rda"]
    cfg, targets, _raw = scene()
    img = focus("fused3", precision="f16")
    got = golden_reports(img, cfg, targets)
    devs = [abs(m["snr_db"] - g["snr_db"])
            for g, m in zip(golden["targets"], got)]
    assert any(not np.isfinite(d) or d > GATE_DB for d in devs), devs


@pytest.mark.slow
def test_sharded_golden_quality_8_devices():
    """Subprocess (8 fake CPU devices): the sharded fused1 lowering must
    hit the same golden corpus — f32 exactly, bs16 within the gate (its
    carried exponents ride the all_to_all corner turns)."""
    code = f"""
import json, numpy as np, jax, jax.numpy as jnp
from repro.core.sar import build_pipeline, metrics, paper_targets, \\
    simulate_cached
from repro.core.sar.geometry import test_scene

golden = json.load(open({GOLDEN_PATH!r}))["families"]["rda"]["targets"]
cfg = test_scene({N})
targets = paper_targets(cfg)
raw = jnp.asarray(np.asarray(simulate_cached(cfg, targets), np.complex64))
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

for precision, exact in ((None, True), ("bs16", False)):
    kw = {{"tune": "off"}}
    if precision is not None:
        kw["precision"] = precision
    img = np.asarray(
        build_pipeline(cfg, "fused1", **kw).lower_sharded(mesh)(raw))
    noise = metrics.noise_rms(img, cfg, targets, guard={GUARD})
    for i, (g, t) in enumerate(zip(golden, targets)):
        rep = metrics.analyze_target(img, cfg, t, noise)
        assert (rep.row, rep.col) == (g["row"], g["col"]), (precision, i)
        dev = abs(rep.snr_db - g["snr_db"])
        if exact:
            assert dev == 0.0, (precision, i, dev)
        else:
            assert dev <= {GATE_DB}, (precision, i, dev)
print("SHARDED_GOLDEN_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC + os.pathsep + os.path.join(SRC, ".."))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "SHARDED_GOLDEN_OK" in r.stdout


def regen():
    """Rewrite the golden fixture from the f32 per-axis references."""
    cfg, targets, _raw = scene()
    doc = {
        "scene_n": N,
        "guard": GUARD,
        "comment": "f32 per-axis reference; regenerate with "
                   "PYTHONPATH=src python tests/test_quality_regression.py"
                   " --regen",
        "families": {},
    }
    for family, (per_axis, _twin) in FAMILIES.items():
        img = focus(per_axis)
        doc["families"][family] = {
            "variant": per_axis,
            "targets": golden_reports(img, cfg, targets),
        }
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH}")


if __name__ == "__main__":
    if "--regen" in sys.argv:
        regen()
    else:
        sys.exit("usage: test_quality_regression.py --regen")
