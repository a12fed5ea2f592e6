"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE first jax use.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — batch
shards over ("pod", "data"); parameters FSDP over "data" (intra-pod ICI),
replicated across pods (gradient all-reduce is the only cross-pod
collective, int8-compressible); tensor/expert parallel over "model".

Every axis is ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint``, which ``jax.make_mesh``'s default Explicit
axes refuse.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host offers (tests / CPU examples)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def activation_rules(mesh) -> dict:
    """Logical->mesh mapping for models.sharding.use_mesh_rules."""
    return {
        "batch": batch_axes(mesh),
        "seq": "model",       # Megatron-style sequence parallelism
        "heads": "model",
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "kv_seq": "data",     # sequence-parallel KV cache (long decode)
        "embed": "data",      # FSDP: parameters shard their d_model dim over
                              # "data" (gathered per layer, ZeRO-3 style)
    }
