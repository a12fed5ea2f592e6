"""§Perf hillclimb driver — the SAR cell (the paper's own workload).

Lowers distributed-RDA schedule variants on the production single-pod mesh
(256 devices) and reports the three roofline terms per variant, plus the
BlockSpec-guaranteed HBM bytes of the real fused kernel (the interpret-mode
HLO materializes the kernel's internals, so its memory term approximates the
UNFUSED pipeline — the analytic kernel bytes are what the Mosaic kernel
moves by construction).

  PYTHONPATH=src python scripts/perf_sar.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import json
import time

import jax
import jax.numpy as jnp

from jax.sharding import AxisType
from repro.core.sar import paper_scene
from repro.core.sar import filters
from repro.core.sar.distributed import build_corner2, build_halo
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh

CFG = paper_scene()
N_PTS = CFG.na * CFG.nr


def analytic_fused_bytes(n_dispatches: int, filter_full_dispatches: int = 0,
                         shared_filters: int = 1) -> int:
    """HBM bytes the Pallas pipeline moves by BlockSpec construction:
    each dispatch reads + writes the full split-complex scene once
    (2 x 2 x 4 bytes per point per dispatch); FULL 2-D filters add one scene
    read; shared/rank-K filters and DFT matrices are O(N) (counted once)."""
    scene = N_PTS * 2 * 4
    total = n_dispatches * 2 * scene
    total += filter_full_dispatches * scene
    total += shared_filters * CFG.nr * 2 * 4
    return total


def measure(name, build_fn, mesh=None, **kw):
    mesh = mesh or make_production_mesh()
    axes = tuple(mesh.axis_names)
    run = build_fn(CFG, mesh, axes=axes, interpret=True, **kw)
    raw = jax.ShapeDtypeStruct((CFG.na, CFG.nr), jnp.complex64)
    t0 = time.time()
    compiled = jax.jit(lambda x: run(x)).lower(raw).compile()
    dt = time.time() - t0
    import math
    model_flops = (2 * 5 * N_PTS * math.log2(CFG.nr)
                   + 2 * 5 * N_PTS * math.log2(CFG.na) + 3 * 6 * N_PTS)
    roof = rf.from_compiled(compiled, mesh.devices.size,
                            model_flops / mesh.devices.size)
    mem = compiled.memory_analysis()
    rec = {
        "variant": name,
        "t_compile_s": round(dt, 1),
        "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                     + mem.temp_size_in_bytes),
        **roof.to_dict(),
    }
    print(f"{name}: t_comp={roof.t_compute*1e6:.1f}us "
          f"t_mem(HLO~unfused)={roof.t_memory*1e6:.1f}us "
          f"t_coll={roof.t_collective*1e6:.1f}us "
          f"colls={roof.collectives.counts} "
          f"link_bytes/dev={roof.collectives.link_bytes/1e6:.2f}MB",
          flush=True)
    return rec


def main():
    out = []
    # baseline: corner2 (2 all-to-alls, 3 fused dispatches, rank-K phases)
    out.append(measure("corner2_256", build_corner2))
    # halo needs halo_cols <= nr/P: at 256 devices the slab is 16 columns ==
    # the halo itself (the exchange degenerates to a corner turn), so the
    # schedule comparison runs at 64 devices where its premise holds.
    mesh64 = jax.make_mesh((64,), ("data",), axis_types=(AxisType.Auto,))
    out.append(measure("corner2_64", build_corner2, mesh=mesh64))
    out.append(measure("halo_64", build_halo, mesh=mesh64))
    # iteration 3: bf16 corner-turn payload (dominant term / 2?)
    out.append(measure("corner2_256_bf16turn", build_corner2,
                       turn_dtype=jnp.bfloat16))

    chips = 256
    for rec in out:
        # analytic fused-kernel HBM term (what Mosaic moves by construction)
        nd = 3 if "corner2" in rec["variant"] else 4
        fb = analytic_fused_bytes(nd)
        chips = 64 if rec["variant"].endswith("_64") else 256
        rec["analytic_fused_hbm_bytes"] = fb
        rec["t_mem_fused_analytic_s"] = fb / chips / rf.HBM_BW
        # unfused baseline: 9 scene round trips (3 RC + 1 azFFT + 1 RCMC +
        # 2 AC + transposes are free in XLA-fused form) — conservative 7
        ub = 7 * 2 * N_PTS * 8
        rec["t_mem_unfused_s"] = ub / chips / rf.HBM_BW
        print(f"{rec['variant']}: analytic fused t_mem="
              f"{rec['t_mem_fused_analytic_s']*1e6:.1f}us vs unfused~"
              f"{rec['t_mem_unfused_s']*1e6:.1f}us; bound="
              f"{max(rec['t_mem_fused_analytic_s'], rec['t_collective_s'], rec['t_compute_s'])*1e6:.1f}us")

    os.makedirs("experiments/perf", exist_ok=True)
    with open("experiments/perf/sar_schedules.json", "w") as f:
        json.dump(out, f, indent=1)
    print("wrote experiments/perf/sar_schedules.json")


if __name__ == "__main__":
    main()
