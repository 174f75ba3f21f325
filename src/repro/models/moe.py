"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Routing: softmax top-k (grok-1: 8e top-2; DeepSeek-V2-Lite: 64e top-6
+ 2 shared experts).  Dispatch builds an (E, C, d) buffer with a sort-
free rank-within-expert computation (cumulative count per expert) —
O(T·k·E) bitwork + O(T·k·d) gathers, never the GShard O(T²) dispatch
einsum.

Expert parallelism: activations in a Megatron-TP transformer are
replicated across the `model` axis between blocks, so each model shard
dispatches its local tokens to its LOCAL experts only and a single psum
over `model` combines expert outputs — EP without all-to-all
(DESIGN.md §7).  `moe_ffn` is the per-shard math; `moe_ffn_sharded`
wraps it in shard_map.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .layers import dense_init, mlp_apply, mlp_init

__all__ = ["MoEConfig", "moe_init", "moe_ffn", "moe_ffn_sharded", "router_topk", "build_dispatch"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                   # per-expert hidden
    n_shared: int = 0           # always-on shared experts (DeepSeek)
    capacity_factor: float = 1.25
    mlp_kind: str = "swiglu"


def moe_init(rng, cfg: MoEConfig, dtype=jnp.float32) -> Dict:
    k_r, k_e, k_s = jax.random.split(rng, 3)
    e = cfg.n_experts
    ke = jax.random.split(k_e, 3)
    params = {
        "router": dense_init(k_r, (cfg.d_model, e), dtype=jnp.float32),
        "experts": {
            "w_gate": dense_init(ke[0], (e, cfg.d_model, cfg.d_ff), dtype=dtype),
            "w_up": dense_init(ke[1], (e, cfg.d_model, cfg.d_ff), dtype=dtype),
            "w_down": dense_init(ke[2], (e, cfg.d_ff, cfg.d_model), dtype=dtype),
        },
    }
    if cfg.n_shared:
        params["shared"] = mlp_init(
            k_s, cfg.d_model, cfg.d_ff * cfg.n_shared, cfg.mlp_kind, dtype=dtype
        )
    return params


def router_topk(router_w: jnp.ndarray, x: jnp.ndarray, top_k: int):
    """x: (T, d) -> (weights (T, k) f32, experts (T, k) i32, aux_loss ())."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    w, idx = jax.lax.top_k(gates, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch-style): E * Σ_e f_e · p_e
    e = router_w.shape[1]
    me = gates.mean(0)
    f = jnp.zeros((e,)).at[idx.reshape(-1)].add(1.0) / idx.size
    aux = e * jnp.sum(f * me)
    return w, idx, aux


def build_dispatch(idx: jnp.ndarray, n_experts: int, capacity: int):
    """Rank each (token, slot) assignment within its expert.

    Returns (positions (T, k) int32 — rank within expert, clipped
    assignments marked by keep mask, counts (E,)).
    Rank computed with a cumulative one-hot sum — deterministic,
    sort-free, O(T·k·E) int adds (E is small relative to T).
    """
    t, k = idx.shape
    flat = idx.reshape(-1)                                       # (T*k,)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)    # (T*k, E)
    ranks_all = jnp.cumsum(onehot, axis=0) - onehot              # rank before self
    pos = jnp.take_along_axis(ranks_all, flat[:, None], axis=1)[:, 0]
    keep = pos < capacity
    counts = onehot.sum(0)
    return pos.reshape(t, k), keep.reshape(t, k), counts


def moe_ffn(params: Dict, x: jnp.ndarray, cfg: MoEConfig,
            capacity: Optional[int] = None):
    """Per-shard MoE FFN. x: (T, d). Returns (out (T, d), aux_loss)."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity or max(8, int(cfg.capacity_factor * t * k / e))

    w, idx, aux = router_topk(params["router"], x, k)
    pos, keep, _ = build_dispatch(idx, e, cap)

    # Scatter tokens into the (E, C, d) buffer.
    buf = jnp.zeros((e, cap, d), x.dtype)
    flat_idx = idx.reshape(-1)
    flat_pos = jnp.where(keep.reshape(-1), pos.reshape(-1), cap)  # cap = drop
    tok = jnp.repeat(jnp.arange(t), k)
    buf = buf.at[flat_idx, flat_pos].set(x[tok], mode="drop")

    # Expert GEMMs (E, C, d) -> (E, C, d).
    ex = params["experts"]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, ex["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, ex["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, ex["w_down"])

    # Gather back with gate weights.
    out_flat = y[flat_idx, jnp.clip(flat_pos, 0, cap - 1)]        # (T*k, d)
    wflat = (w.reshape(-1) * keep.reshape(-1)).astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[tok].add(out_flat * wflat[:, None])

    if cfg.n_shared:
        out = out + mlp_apply(params["shared"], x, cfg.mlp_kind)
    return out, aux


def moe_ffn_sharded(params: Dict, x: jnp.ndarray, cfg: MoEConfig, mesh,
                    model_axis: str = "model", data_axes=("data",),
                    fsdp: bool = False):
    """shard_map MoE: tokens sharded over the data axes and replicated
    along `model` (they are, between Megatron blocks); one psum over
    `model` combines expert outputs — no all-to-all (DESIGN.md §7).

    Two regimes on the `model` axis:
      EP  (E % M == 0): each shard owns E/M whole experts.
      TP  (M % E == 0, e.g. grok-1's 8e on a 16-way axis): every shard
          owns a 1/(M) slice of every expert's d_ff; the same psum that
          combines experts also combines the ff partial sums.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[model_axis]
    fsdp = fsdp and "data" in mesh.shape
    if cfg.n_experts % n_shards != 0:
        assert cfg.d_ff % n_shards == 0, "need E%M==0 or d_ff%M==0"
        return _moe_ffn_sharded_tp(params, x, cfg, mesh, model_axis, data_axes, fsdp)
    e_local = cfg.n_experts // n_shards

    def local_fn(p_local, x_local):
        if fsdp:
            # ZeRO-3 for the expert bulk: gather the `data`-sharded slice
            # HERE, inside the remat region, so backward RE-GATHERS
            # instead of stashing 64 layers of gathered weights
            # (grok-1: 37.7 GiB/device saved; EXPERIMENTS.md §Perf).
            ex = p_local["experts"]
            p_local = dict(p_local)
            p_local["experts"] = {
                "w_gate": jax.lax.all_gather(ex["w_gate"], "data", axis=1, tiled=True),
                "w_up": jax.lax.all_gather(ex["w_up"], "data", axis=1, tiled=True),
                "w_down": jax.lax.all_gather(ex["w_down"], "data", axis=2, tiled=True),
            }
        # Global top-k routing (router replicated), then keep only the
        # assignments that land on this shard's experts.
        w, idx, aux = router_topk(p_local["router"], x_local, cfg.top_k)
        shard = jax.lax.axis_index(model_axis)
        lo = shard * e_local
        local = (idx >= lo) & (idx < lo + e_local)
        idx_l = jnp.where(local, idx - lo, e_local)               # e_local = drop bucket
        t = x_local.shape[0]
        cap = max(8, int(cfg.capacity_factor * t * cfg.top_k / cfg.n_experts))
        pos, keep, _ = build_dispatch(idx_l, e_local + 1, cap)
        keep = keep & local

        buf = jnp.zeros((e_local + 1, cap, x_local.shape[1]), x_local.dtype)
        flat_idx = idx_l.reshape(-1)
        flat_pos = jnp.where(keep.reshape(-1), pos.reshape(-1), cap)
        tok = jnp.repeat(jnp.arange(t), cfg.top_k)
        buf = buf.at[flat_idx, flat_pos].set(x_local[tok], mode="drop")
        buf = buf[:e_local]

        ex = p_local["experts"]
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, ex["w_gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", buf, ex["w_up"])
        y = jnp.einsum("ecf,efd->ecd", h, ex["w_down"])

        safe_idx = jnp.minimum(flat_idx, e_local - 1)
        out_flat = y[safe_idx, jnp.clip(flat_pos, 0, cap - 1)]
        wflat = (w.reshape(-1) * keep.reshape(-1)).astype(x_local.dtype)
        out = jnp.zeros_like(x_local).at[tok].add(out_flat * wflat[:, None])
        out = jax.lax.psum(out, model_axis)
        if cfg.n_shared:
            out = out + mlp_apply(p_local["shared"], x_local, cfg.mlp_kind)
        return out, jax.lax.pmean(aux, model_axis)

    pspec_params = jax.tree_util.tree_map(lambda _: P(), params)
    if fsdp:
        pspec_params["experts"] = {
            "w_gate": P(model_axis, "data", None),
            "w_up": P(model_axis, "data", None),
            "w_down": P(model_axis, None, "data"),
        }
    else:
        pspec_params["experts"] = jax.tree_util.tree_map(
            lambda _: P(model_axis), params["experts"]
        )
    xspec = P(data_axes) if data_axes else P()
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspec_params, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(params, x)


def _moe_ffn_sharded_tp(params: Dict, x: jnp.ndarray, cfg: MoEConfig, mesh,
                        model_axis: str, data_axes, fsdp: bool = False):
    """TP regime: every shard holds (E, d, d_ff/M) slices; capacity
    dispatch is identical on all shards, the psum combines ff partials."""
    from jax.sharding import PartitionSpec as P

    def local_fn(p_local, x_local):
        if fsdp:
            ex = p_local["experts"]
            p_local = dict(p_local)
            p_local["experts"] = {
                "w_gate": jax.lax.all_gather(ex["w_gate"], "data", axis=1, tiled=True),
                "w_up": jax.lax.all_gather(ex["w_up"], "data", axis=1, tiled=True),
                "w_down": jax.lax.all_gather(ex["w_down"], "data", axis=2, tiled=True),
            }
        w, idx, aux = router_topk(p_local["router"], x_local, cfg.top_k)
        t = x_local.shape[0]
        e = cfg.n_experts
        cap = max(8, int(cfg.capacity_factor * t * cfg.top_k / e))
        pos, keep, _ = build_dispatch(idx, e, cap)

        buf = jnp.zeros((e, cap, x_local.shape[1]), x_local.dtype)
        flat_idx = idx.reshape(-1)
        flat_pos = jnp.where(keep.reshape(-1), pos.reshape(-1), cap)
        tok = jnp.repeat(jnp.arange(t), cfg.top_k)
        buf = buf.at[flat_idx, flat_pos].set(x_local[tok], mode="drop")

        ex = p_local["experts"]                       # ff-sliced locally
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, ex["w_gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", buf, ex["w_up"])
        y = jnp.einsum("ecf,efd->ecd", h, ex["w_down"])   # partial over ff

        out_flat = y[flat_idx, jnp.clip(flat_pos, 0, cap - 1)]
        wflat = (w.reshape(-1) * keep.reshape(-1)).astype(x_local.dtype)
        out = jnp.zeros_like(x_local).at[tok].add(out_flat * wflat[:, None])
        out = jax.lax.psum(out, model_axis)
        if cfg.n_shared:
            out = out + mlp_apply(p_local["shared"], x_local, cfg.mlp_kind)
        return out, jax.lax.pmean(aux, model_axis)

    pspec_params = jax.tree_util.tree_map(lambda _: P(), params)
    d_ax = "data" if fsdp else None
    pspec_params["experts"] = {
        "w_gate": P(None, d_ax, model_axis),
        "w_up": P(None, d_ax, model_axis),
        "w_down": P(None, model_axis, d_ax),
    }
    xspec = P(data_axes) if data_axes else P()
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(pspec_params, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(params, x)
