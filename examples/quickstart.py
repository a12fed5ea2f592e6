"""Quickstart: the paper's fused kernel in three calls.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.runtime import use_compile_cache
from repro.kernels import ops, ref
from repro.core.sar import (build_pipeline, metrics, paper_targets, simulate,
                            test_scene)

use_compile_cache()

# --- 1. One fused dispatch: FFT -> matched filter -> IFFT ------------------
rng = np.random.default_rng(0)
xr = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)   # 8 range lines
xi = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)
hr = jnp.asarray(rng.standard_normal(4096), jnp.float32)        # matched filter
hi = jnp.asarray(rng.standard_normal(4096), jnp.float32)

yr, yi = ops.fused_fft_mult_ifft_rows(xr, xi, hr, hi)           # ONE dispatch
wr, wi = ref.spectral_ref(xr, xi, axis=1, fwd=True, inv=True,
                          hr=hr[None], hi=hi[None])             # 3-stage oracle
err = float(jnp.max(jnp.abs(yr - wr)))
print(f"fused kernel vs unfused oracle: max|err| = {err:.2e}")

# --- 2. A full SAR scene through the fused Range Doppler pipeline ----------
cfg = test_scene(256)
targets = paper_targets(cfg)
raw = simulate(cfg, targets)                  # chirp echo + 20 dB noise
image = build_pipeline(cfg, "fused3").run(raw)  # 3 fused dispatches total

# --- 3. Point-target quality (the paper's Table IV metrics) ----------------
# (PSLR/ISLR need the 512^2 scene where targets don't share sidelobe
#  windows — see examples/sar_e2e.py and tests/test_sar.py)
for i, rep in enumerate(metrics.analyze_scene(np.asarray(image), cfg, targets)):
    print(f"target {i}: peak@({rep.row},{rep.col}) snr={rep.snr_db:.1f} dB")
