"""Multi-device behaviour (8 fake CPU devices, subprocess-isolated so the
main test process keeps the host's real device count)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_distributed_sar_corner2_and_halo():
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.core.sar import test_scene, paper_targets, simulate, build_pipeline, metrics
from repro.core.sar.distributed import build_corner2, build_halo

cfg = test_scene(256)
targets = paper_targets(cfg)
raw = simulate(cfg, targets)
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))

f3 = np.asarray(build_pipeline(cfg, "fused3").run(raw))
img = np.asarray(build_corner2(cfg, mesh)(raw))
assert float(np.max(np.abs(img - f3))) == 0.0, "corner2 != fused3"

un = np.asarray(build_pipeline(cfg, "unfused").run(raw))
img_h = np.asarray(build_halo(cfg, mesh)(raw))
c = metrics.compare_pipelines(img_h, un, cfg, targets)
assert c["l2_relative_error"] < 1e-5, c["l2_relative_error"]
assert max(c["snr_delta_db"]) < 0.01

# multi-axis mesh (pod x data)
mesh2 = jax.make_mesh((2, 4), ("pod", "data"),
                      axis_types=(AxisType.Auto,) * 2)
img2 = np.asarray(build_corner2(cfg, mesh2, axes=("pod", "data"))(raw))
assert float(np.max(np.abs(img2 - img))) == 0.0
print("DIST_SAR_OK")
""")
    assert "DIST_SAR_OK" in out


@pytest.mark.slow
def test_compressed_psum_matches_mean():
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp, functools
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P
from repro.optim import compress

mesh = jax.make_mesh((8,), ("dp",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
e = jnp.zeros((8, 64), jnp.float32)

@functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
                   out_specs=(P("dp"), P("dp")))
def f(gl, el):
    m, ne = compress.compressed_psum({"g": gl}, {"g": el}, "dp")
    return m["g"], ne["g"]

mean, new_e = f(g, e)
true_mean = np.tile(np.asarray(g).mean(0), (8, 1))
err = np.abs(np.asarray(mean) - true_mean).max()
amax = np.abs(np.asarray(g)).max()
assert err < 2 * amax / 127.0, (err, amax / 127)
# error feedback residual bounded by one quant step per shard
assert np.abs(np.asarray(new_e)).max() <= amax / 127.0 + 1e-6
print("COMPRESS_OK", err)
""")
    assert "COMPRESS_OK" in out


@pytest.mark.slow
def test_lm_sharded_train_step_matches_single_device():
    """One train step under a 4x2 (data x model) mesh == single-device."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import registry
from repro.launch import sharding as shd
from repro.launch.mesh import activation_rules
from repro.launch import steps as steps_mod
from repro.models import Model, use_mesh_rules
from repro.optim import AdamWConfig, adamw
from repro.data import DataConfig, TokenStream

cfg = registry.smoke("minitron-4b", seq=64)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
opt = adamw.init(params)
data = TokenStream(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                              global_batch=8))
batch = data.batch(0)
ocfg = AdamWConfig(warmup_steps=0)

# single device
step = steps_mod.build_train_step(model, ocfg)
p1, s1, st1 = jax.jit(step)(params, opt, batch)

# sharded. The jitted callable MUST be a fresh function object traced inside
# the mesh-rules context (exactly how launch/train.py builds it): jax's
# trace cache is keyed on the function object, so re-jitting the same
# `step` would silently reuse the jaxpr traced OUTSIDE the context — no
# sharding constraints, no ZeRO-3 use-site gather, and bf16 partial-sum
# contractions over the FSDP-sharded dims that drift the loss by units.
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rules = activation_rules(mesh)
p_sh = shd.param_shardings(params, cfg, mesh, rules)
params_s = jax.device_put(params, p_sh)
opt_s = adamw.init(params_s)
with use_mesh_rules(mesh, rules):
    step_s = steps_mod.build_train_step(model, ocfg)
    p2, s2, st2 = jax.jit(step_s)(params_s, opt_s, batch)

l1, l2 = float(st1["loss"]), float(st2["loss"])
assert abs(l1 - l2) < 5e-3, (l1, l2)
d = max(float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert d < 5e-3, d
print("SHARDED_TRAIN_OK", l1, l2, d)
""")
    assert "SHARDED_TRAIN_OK" in out


@pytest.mark.slow
def test_long_decode_seq_parallel_kv():
    """Batch-1 decode with a sequence-sharded KV cache == single device."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import registry
from repro.launch import sharding as shd
from repro.launch.mesh import activation_rules
from repro.models import Model, use_mesh_rules

cfg = registry.smoke("gemma3-12b", seq=64)
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0,
                          cfg.vocab_size, jnp.int32)
cache, _ = model.prefill(params, {"tokens": toks[:, :63]}, max_len=64)
l1, _ = model.decode_step(params, cache, toks[:, 63:64])

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rules = activation_rules(mesh)
with use_mesh_rules(mesh, rules):
    c_sh = shd.cache_shardings(jax.eval_shape(lambda: cache), cfg, mesh,
                               rules, batch=1)
    cache_s = jax.device_put(cache, c_sh)
    l2, _ = jax.jit(model.decode_step)(params, cache_s, toks[:, 63:64])
d = float(jnp.max(jnp.abs(l1 - l2)))
assert d < 5e-3, d
print("SEQPAR_DECODE_OK", d)
""")
    assert "SEQPAR_DECODE_OK" in out
