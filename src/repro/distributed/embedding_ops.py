"""Distributed embedding lookup (the sharded EmbeddingBag).

Tables row-shard over `model`; a shard_map local mask-gather + psum
implements the lookup without ever all-gathering the table — grads
transpose to scatter-adds that stay sharded.  This is the TPU analogue
of a parameter-server embedding shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["sharded_lookup", "sharded_lookup_rs", "sharded_bag_sum"]


def sharded_lookup(table: jnp.ndarray, idx: jnp.ndarray, mesh,
                   data_axes=("data",), model_axis: str = "model") -> jnp.ndarray:
    """table (V, E) sharded P(model, None); idx (B, F) sharded over data.
    Returns (B, F, E) embeddings sharded over data."""
    v = table.shape[0]
    m = mesh.shape[model_axis]
    vloc = v // m

    def local(tbl, ids):
        shard = lax.axis_index(model_axis)
        loc = ids - shard * vloc
        ok = (loc >= 0) & (loc < vloc)
        rows = jnp.take(tbl, jnp.clip(loc, 0, vloc - 1), axis=0)
        rows = rows * ok[..., None].astype(rows.dtype)
        return lax.psum(rows, model_axis)

    ispec = P(data_axes, None) if data_axes else P()
    ospec = P(data_axes, None, None) if data_axes else P()
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(model_axis, None), ispec),
        out_specs=ospec,
        check_vma=False,
    )(table, idx)


def sharded_bag_sum(table: jnp.ndarray, idx: jnp.ndarray, mesh,
                    data_axes=("data",), model_axis: str = "model") -> jnp.ndarray:
    """EmbeddingBag(sum) over row-sharded table: (B, L) ids → (B, E)."""
    v = table.shape[0]
    m = mesh.shape[model_axis]
    vloc = v // m

    def local(tbl, ids):
        shard = lax.axis_index(model_axis)
        loc = ids - shard * vloc
        ok = (loc >= 0) & (loc < vloc) & (ids >= 0)
        rows = jnp.take(tbl, jnp.clip(loc, 0, vloc - 1), axis=0)
        rows = rows * ok[..., None].astype(rows.dtype)
        return lax.psum(rows.sum(1), model_axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(model_axis, None), P(data_axes, None)),
        out_specs=P(data_axes, None),
        check_vma=False,
    )(table, idx)


def sharded_lookup_rs(table: jnp.ndarray, idx: jnp.ndarray, mesh,
                      data_axes=("data",), model_axis: str = "model") -> jnp.ndarray:
    """Reduce-scatter lookup: output batch shards over `model` too.

    The plain psum moves the full (B_loc, F, E) partial per shard even
    though 15/16 of each shard's entries are zeros (a table row lives on
    exactly one shard).  psum_scatter moves half the bytes of the
    all-reduce AND leaves the batch sharded over `model`, so the dense
    tower downstream runs on B/(dp·model) rows per device — 16x less
    compute/memory than the replicated-over-model baseline
    (EXPERIMENTS.md §Perf hillclimb #2).
    idx (B, F) sharded over data -> (B, F, E) sharded over data+model.
    """
    v = table.shape[0]
    m = mesh.shape[model_axis]
    vloc = v // m

    def local(tbl, ids):
        shard = lax.axis_index(model_axis)
        loc = ids - shard * vloc
        ok = (loc >= 0) & (loc < vloc)
        rows = jnp.take(tbl, jnp.clip(loc, 0, vloc - 1), axis=0)
        rows = rows * ok[..., None].astype(rows.dtype)           # (B_loc, F, E)
        return lax.psum_scatter(rows, model_axis, scatter_dimension=0, tiled=True)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(model_axis, None), P(data_axes, None)),
        out_specs=P(data_axes + (model_axis,), None, None),
        check_vma=False,
    )(table, idx)
