"""Elastic scaling: re-mesh and re-shard when the device pool changes.

Checkpoints store LOGICAL (unsharded) arrays (checkpoint.py), so a job
preempted on 2×16×16 can resume on 16×16 (or any factorization): build
the new mesh, re-derive PartitionSpecs from the same rules, device_put.
Divisibility is validated up front so a bad pool fails fast with a
report instead of an XLA error mid-restore.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["plan_mesh", "plan_mesh_shape", "validate_specs", "reshard_tree"]


def plan_mesh_shape(n_devices: int, prefer_model: int = 16):
    """Largest model-axis ≤ prefer_model that divides n_devices."""
    for m in range(min(prefer_model, n_devices), 0, -1):
        if n_devices % m == 0:
            return (n_devices // m, m)
    raise ValueError(f"cannot factor {n_devices} devices")


def plan_mesh(n_devices: int, prefer_model: int = 16):
    """Pick a (data, model) mesh for an arbitrary device count."""
    from repro.launch.mesh import make_mesh
    return make_mesh(plan_mesh_shape(n_devices, prefer_model), ("data", "model"))


def validate_specs(tree: Any, specs: Any, mesh) -> List[str]:
    """Return human-readable problems (empty list = clean)."""
    problems = []
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat_s = treedef.flatten_up_to(specs)
    for leaf, spec in zip(flat, flat_s):
        if not isinstance(spec, P):
            continue
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if dim >= len(leaf.shape) or leaf.shape[dim] % size != 0:
                problems.append(
                    f"dim {dim} of shape {leaf.shape} not divisible by "
                    f"{axes}={size}")
    return problems


def reshard_tree(tree: Any, specs: Any, mesh) -> Any:
    """device_put every leaf with its spec on the (new) mesh."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    flat_s = treedef.flatten_up_to(specs)
    out = [jax.device_put(l, NamedSharding(mesh, s)) if isinstance(s, P)
           else jax.device_put(l) for l, s in zip(flat, flat_s)]
    return jax.tree_util.tree_unflatten(treedef, out)
