"""Device mesh construction and audit-sweep sharding.

The audit cross-product (objects × constraints, SURVEY.md §2.5) shards
over a 2-D mesh:

  * "data"  — the object/review axis (N): each device evaluates a slab of
    the cluster inventory. The pure data-parallel dimension; scales to
    multi-host over DCN with no cross-device traffic during evaluation.
  * "model" — the constraint axis (C): parameter tensors shard across
    devices when constraint sets are large (the analog of tensor/model
    parallelism; verdict aggregation all-gathers over ICI).

The evaluator function itself (ir/evaljax.py) is pure and shape-static, so
sharding is entirely in the data layout: annotate inputs with
NamedSharding and let XLA insert the collectives (the scaling-book recipe:
pick a mesh, annotate, let the compiler do the rest). shard_map is used
where the collective must be explicit (per-constraint violation counts
psum'd over the data axis in parallel/collectives.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def shard_map_wrap(f, mesh, in_specs, out_specs):
    """shard_map with replication checking disabled (evaljax's mesh
    sweep and collectives' audit step both route through it)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(devices=None, data: Optional[int] = None,
              model: int = 1) -> Mesh:
    """Mesh over the available devices, data-major.

    Default: all devices on the data axis (objects), model=1. For very
    large constraint sets pass model>1 to shard parameters too.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data is None:
        if n % model:
            raise ValueError(f"{n} devices not divisible by model={model}")
        data = n // model
    if data * model != n:
        raise ValueError(f"mesh {data}x{model} != {n} devices")
    arr = np.array(devices).reshape(data, model)
    return Mesh(arr, ("data", "model"))


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def pad_batch(feats: dict, n_mult: int) -> tuple[dict, int]:
    """Pad every [N, ...] feature array so N divides the data axis."""
    out = {}
    n_old = None
    for slot, arrs in feats.items():
        out[slot] = {}
        for name, a in arrs.items():
            n_old = a.shape[0]
            n_new = _pad_to(n_old, n_mult)
            if n_new != n_old:
                pad = [(0, n_new - n_old)] + [(0, 0)] * (a.ndim - 1)
                a = np.pad(a, pad)
            out[slot][name] = a
    return out, (n_old if n_old is not None else 0)


def shard_features(feats: dict, mesh: Mesh) -> dict:
    """Place feature arrays sharded on the data axis (leading N dim)."""
    out = {}
    for slot, arrs in feats.items():
        out[slot] = {}
        for name, a in arrs.items():
            spec = P("data", *([None] * (a.ndim - 1)))
            out[slot][name] = jax.device_put(
                a, NamedSharding(mesh, spec))
    return out


def shard_params(params: dict, mesh: Mesh, shard_c: bool = False) -> dict:
    """Constraint tensors: replicated by default; sharded over "model"
    when the constraint set is large."""
    out = {}
    for slot, arrs in params.items():
        out[slot] = {}
        for name, a in arrs.items():
            if shard_c:
                spec = P("model", *([None] * (a.ndim - 1)))
            else:
                spec = P(*([None] * a.ndim))
            out[slot][name] = jax.device_put(a, NamedSharding(mesh, spec))
    return out


def replicate(x, mesh: Mesh):
    return jax.device_put(x, NamedSharding(mesh, P(*([None] * np.ndim(x)))))
