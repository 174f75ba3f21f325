"""Per-cell step builders: for every (arch × shape) pair, produce the
function the dry-run lowers plus its abstract inputs and shardings.

This is the single source of truth shared by launch/dryrun.py (lower +
compile on the production mesh), launch/train.py / serve.py (real
execution), and the per-arch smoke tests (reduced configs, 1 device).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchDef, get_arch
from repro.distributed.sharding_rules import (
    data_axes, gnn_param_specs, kv_cache_specs, lm_param_specs,
    recsys_param_specs, spec_tree, zero1_state_specs,
)
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm

__all__ = ["CellSpec", "build_cell", "REDUCED_SHAPES"]


@dataclasses.dataclass
class CellSpec:
    arch_id: str
    shape_name: str
    fn: Callable                     # the step to lower / run
    args: Tuple[Any, ...]            # ShapeDtypeStructs (dry-run) or arrays
    in_shardings: Any
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()
    static_note: str = ""


# Reduced per-kind shapes used by smoke tests (CPU, 1 device).
REDUCED_SHAPES = {
    "train": dict(global_batch=4, seq_len=64),
    "prefill": dict(global_batch=2, seq_len=64),
    "decode": dict(global_batch=4, seq_len=64),
    "train_graph": dict(n_nodes=128, n_edges=512, d_feat=16, n_classes=7),
    "train_minibatch": dict(n_nodes=256, n_edges=2048, batch_nodes=16,
                            fanout=(5, 3), d_feat=16, n_classes=7),
    "train_batched_graphs": dict(n_nodes=10, n_edges=20, batch=8, d_feat=16,
                                 n_classes=2),
    "train_recsys": dict(batch=64),
    "serve": dict(batch=32),
    "retrieval": dict(batch=1, n_candidates=2048),
    "serve_websearch": dict(query_batch=8),
    "train_websearch": dict(query_batch=8),
}


def _sd(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def _named(mesh, spec_tree_):
    if mesh is None:
        return None
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree_,
        is_leaf=lambda x: isinstance(x, P),
    )


def _dp(mesh) -> Tuple[str, ...]:
    return data_axes(mesh) if mesh is not None else ()


def _dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in _dp(mesh)])) if mesh else 1


# ======================================================================== LM
def _lm_opt_cfg(reduced: bool) -> AdamWConfig:
    # bf16 moments halve optimizer HBM on the big configs (grok-1 fits).
    return AdamWConfig(lr=1e-4, weight_decay=0.01,
                       state_dtype=jnp.float32 if reduced else jnp.bfloat16)


def make_lm_train_step(cfg, mesh, opt_cfg: AdamWConfig, param_specs=None):
    """Loss + grads (+ optional gradient-accumulation microbatching) +
    clip + AdamW.  Microbatching divides activation memory by `mb`
    (measured 3x on starcoder2 train_4k); the accumulator is explicitly
    constrained to the parameter sharding — without the constraint GSPMD
    replicates FSDP expert grads over `data` (37 GiB/device on grok-1;
    EXPERIMENTS.md §Perf)."""
    from repro.models.transformer import lm_loss

    mb = max(1, cfg.microbatch)

    def constrain(tree):
        # Only FSDP configs need the explicit accumulator constraint; for
        # TP-only params GSPMD already picks the param sharding, and the
        # constraint forces extra resharding copies (deepseek: +6 GiB).
        if mesh is None or param_specs is None or not getattr(cfg, "fsdp", False):
            return tree
        return jax.tree_util.tree_map(
            lambda t, sp: jax.lax.with_sharding_constraint(
                t, jax.sharding.NamedSharding(mesh, sp)),
            tree, param_specs)

    def loss_fn(p, tokens, targets):
        return lm_loss(p, tokens, targets, cfg, mesh)

    def train_step(params, opt_state, tokens, targets):
        if mb == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
            grads = constrain(grads)
        else:
            b = tokens.shape[0] // mb
            tk = tokens.reshape(mb, b, -1)
            tg = targets.reshape(mb, b, -1)

            acc_dt = getattr(cfg, "grad_accum_dtype", jnp.float32)

            def mb_step(carry, xs):
                gacc, lacc = carry
                l, g = jax.value_and_grad(loss_fn)(params, xs[0], xs[1])
                gacc = jax.tree_util.tree_map(
                    lambda a, c: (a.astype(jnp.float32) + c.astype(jnp.float32)).astype(acc_dt),
                    gacc, g)
                return (constrain(gacc), lacc + l), None

            zero = constrain(jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dt), params))
            (grads, loss), _ = jax.lax.scan(mb_step, (zero, jnp.float32(0.0)), (tk, tg))
            grads = jax.tree_util.tree_map(lambda g: g / mb, grads)
            loss = loss / mb
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        params, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def _build_lm(arch: ArchDef, shape_name: str, mesh, reduced: bool) -> CellSpec:
    from repro.models.transformer import (
        decode_step, init_kv_cache, init_params, prefill,
    )

    cfg = arch.model_cfg(reduced)
    spec = arch.shape(shape_name)
    sp = dict(REDUCED_SHAPES[spec.kind]) if reduced else dict(spec.params)
    b, s = sp["global_batch"], sp["seq_len"]
    dp = _dp(mesh)
    msize = mesh.shape["model"] if mesh else None

    params_abs = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    p_specs = lm_param_specs(params_abs, msize, getattr(cfg, "fsdp", False),
                             getattr(cfg, "zero3", False))

    if spec.kind == "train":
        opt_cfg = _lm_opt_cfg(reduced)
        opt_abs = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), params_abs)
        # moments shard like params + ZeRO-1 over `data`
        mom_specs = (zero1_state_specs(params_abs, p_specs, mesh)
                     if mesh is not None else jax.tree_util.tree_map(lambda _: P(), params_abs))
        o_specs = {"mu": mom_specs, "nu": mom_specs, "count": P()}
        tok_axes = (dp + ("model",)) if getattr(cfg, "zero3", False) and dp else dp
        tok_spec = P(tok_axes if tok_axes else None, None)

        fn = make_lm_train_step(cfg, mesh, opt_cfg, param_specs=p_specs)
        args = (params_abs, opt_abs,
                _sd((b, s), jnp.int32), _sd((b, s), jnp.int32))
        in_sh = (_named(mesh, p_specs), _named(mesh, o_specs),
                 _named(mesh, tok_spec), _named(mesh, tok_spec))
        out_sh = (_named(mesh, p_specs), _named(mesh, o_specs),
                  _named(mesh, {"loss": P(), "grad_norm": P()}))
        return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, out_sh,
                        donate_argnums=(0, 1))

    if spec.kind == "prefill":
        fn = lambda params, tokens: prefill(params, tokens, cfg, mesh)
        cache_abs = jax.eval_shape(lambda: init_kv_cache(cfg, b, s))
        c_specs = kv_cache_specs(cache_abs, mesh) if mesh else None
        args = (params_abs, _sd((b, s), jnp.int32))
        in_sh = (_named(mesh, p_specs), _named(mesh, P(dp if dp else None, None)))
        out_sh = ((_named(mesh, P(dp if dp else None, "model")), _named(mesh, c_specs))
                  if mesh else None)
        return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, out_sh)

    # decode (decode_32k / long_500k): one new token against an S-token cache
    fn = lambda params, token, cache, pos: decode_step(params, token, cache, pos, cfg, mesh)
    cache_abs = jax.eval_shape(lambda: init_kv_cache(cfg, b, s))
    c_specs = kv_cache_specs(cache_abs, mesh) if mesh else None
    bspec = P(dp) if (mesh and b % _dp_size(mesh) == 0 and b >= _dp_size(mesh)) else P()
    args = (params_abs, _sd((b,), jnp.int32), cache_abs, _sd((b,), jnp.int32))
    in_sh = (_named(mesh, p_specs), _named(mesh, bspec), _named(mesh, c_specs),
             _named(mesh, bspec))
    out_sh = ((_named(mesh, P(bspec[0] if bspec else None, "model")),
               _named(mesh, c_specs)) if mesh else None)
    return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, out_sh,
                    donate_argnums=(2,))


# ======================================================================= GNN
def _build_gnn(arch: ArchDef, shape_name: str, mesh, reduced: bool) -> CellSpec:
    from repro.models.gnn import (
        SAGEConfig, sage_block_forward, sage_full_forward, sage_graph_forward,
        sage_init,
    )
    from repro.models.layers import dense_init

    spec = arch.shape(shape_name)
    sp = dict(REDUCED_SHAPES[spec.kind]) if reduced else dict(spec.params)
    base = arch.model_cfg(reduced)
    cfg = SAGEConfig(d_in=sp["d_feat"], d_hidden=base.d_hidden,
                     n_classes=sp["n_classes"], n_layers=base.n_layers,
                     aggregator=base.aggregator)
    all_axes = (_dp(mesh) + ("model",)) if mesh else ()
    n_dev = (int(np.prod(list(mesh.shape.values()))) if mesh else 1)
    opt_cfg = AdamWConfig(lr=1e-3)

    def ce_loss(logits, labels, mask):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        gold = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        return -jnp.sum(gold * mask) / jnp.maximum(mask.sum(), 1.0)

    p_abs = jax.eval_shape(lambda: sage_init(jax.random.key(0), cfg))
    p_specs = gnn_param_specs(p_abs, mesh.shape["model"] if mesh else None)
    edge_spec = P(None, all_axes if all_axes else None)

    def pad_e(e: int) -> int:
        return ((e + n_dev - 1) // n_dev) * n_dev

    if spec.kind in ("train_graph", "train_minibatch", "train_batched_graphs"):
        if spec.kind == "train_graph":
            n, e = sp["n_nodes"], pad_e(sp["n_edges"])

            def fn(params, opt_state, feats, edges, labels, mask):
                def loss_fn(p):
                    logits = sage_full_forward(p, cfg, feats, edges)
                    return ce_loss(logits, labels, mask)
                loss, grads = jax.value_and_grad(loss_fn)(params)
                params, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
                return params, opt_state, loss

            opt_abs = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), p_abs)
            args = (p_abs, opt_abs, _sd((n, sp["d_feat"]), jnp.float32),
                    _sd((2, e), jnp.int32), _sd((n,), jnp.int32),
                    _sd((n,), jnp.float32))
            in_sh = (_named(mesh, p_specs), _named(mesh, jax.tree_util.tree_map(lambda _: P(), opt_abs)),
                     _named(mesh, P()), _named(mesh, edge_spec),
                     _named(mesh, P()), _named(mesh, P()))
            out_sh = None
            return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, out_sh,
                            donate_argnums=(0, 1))

        if spec.kind == "train_minibatch":
            bn = sp["batch_nodes"]
            f_out, f_in = sp["fanout"]          # e.g. (15, 10): inner, outer
            # fixed frontier/edge budgets (sampler pads up to these)
            e1 = bn * f_in
            fr1 = bn + e1
            e0 = fr1 * f_out
            fr0 = fr1 + e0

            def fn(params, opt_state, feats, src0, dst0, src1, dst1, labels):
                blocks = [(src0, dst0, fr1), (src1, dst1, bn)]

                def loss_fn(p):
                    logits = sage_block_forward(p, cfg, feats, blocks)
                    return ce_loss(logits, labels, jnp.ones((bn,), jnp.float32))
                loss, grads = jax.value_and_grad(loss_fn)(params)
                params, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
                return params, opt_state, loss

            opt_abs = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), p_abs)
            e0p, e1p = pad_e(e0), pad_e(e1)
            args = (p_abs, opt_abs, _sd((fr0, sp["d_feat"]), jnp.float32),
                    _sd((e0p,), jnp.int32), _sd((e0p,), jnp.int32),
                    _sd((e1p,), jnp.int32), _sd((e1p,), jnp.int32),
                    _sd((bn,), jnp.int32))
            evec = P(all_axes if all_axes else None)
            in_sh = (_named(mesh, p_specs),
                     _named(mesh, jax.tree_util.tree_map(lambda _: P(), opt_abs)),
                     _named(mesh, P()), _named(mesh, evec), _named(mesh, evec),
                     _named(mesh, evec), _named(mesh, evec), _named(mesh, P()))
            return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None,
                            donate_argnums=(0, 1))

        # train_batched_graphs (molecule)
        bsz, npg, epg = sp["batch"], sp["n_nodes"], sp["n_edges"]
        n, e = bsz * npg, pad_e(bsz * epg)
        readout_abs = jax.eval_shape(lambda: {
            "w": dense_init(jax.random.key(1), (cfg.n_classes, sp["n_classes"])),
            "b": jnp.zeros((sp["n_classes"],)),
        })

        def fn(params, readout, opt_state, feats, edges, graph_id, labels):
            def loss_fn(pr):
                p, r = pr
                logits = sage_graph_forward(p, cfg, feats, edges, graph_id, bsz, r)
                return ce_loss(logits, labels, jnp.ones((bsz,), jnp.float32))
            loss, grads = jax.value_and_grad(loss_fn)((params, readout))
            (params, readout), opt_state = adamw_update(
                (params, readout), grads, opt_state, opt_cfg)
            return params, readout, opt_state, loss

        opt_abs = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), (p_abs, readout_abs))
        args = (p_abs, readout_abs, opt_abs, _sd((n, sp["d_feat"]), jnp.float32),
                _sd((2, e), jnp.int32), _sd((n,), jnp.int32), _sd((bsz,), jnp.int32))
        in_sh = (_named(mesh, p_specs), _named(mesh, jax.tree_util.tree_map(lambda _: P(), readout_abs)),
                 _named(mesh, jax.tree_util.tree_map(lambda _: P(), opt_abs)),
                 _named(mesh, P()), _named(mesh, edge_spec), _named(mesh, P()),
                 _named(mesh, P()))
        return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None,
                        donate_argnums=(0, 1, 2))

    raise ValueError(spec.kind)


# ==================================================================== recsys
def _build_recsys(arch: ArchDef, shape_name: str, mesh, reduced: bool) -> CellSpec:
    from repro.models import recsys as R

    spec = arch.shape(shape_name)
    kind = "train_recsys" if spec.kind == "train" else spec.kind
    sp = dict(REDUCED_SHAPES[kind]) if reduced else dict(spec.params)
    cfg = arch.model_cfg(reduced)
    dp = _dp(mesh)
    bspec = P(dp if dp else None, None)
    opt_cfg = AdamWConfig(lr=1e-3)

    is_b4r = arch.arch_id == "bert4rec"

    if is_b4r:
        p_abs = jax.eval_shape(lambda: R.bert4rec_init(jax.random.key(0), cfg))
    else:
        init = {"wide-deep": R.wide_deep_init, "deepfm": R.deepfm_init,
                "dcn-v2": R.dcn_init}[arch.arch_id]
        p_abs = jax.eval_shape(lambda: init(jax.random.key(0), cfg))
    p_specs = recsys_param_specs(p_abs, mesh.shape["model"] if mesh else None)

    def ctr_forward(params, sparse, dense):
        if arch.arch_id == "wide-deep":
            return R.wide_deep_forward(params, sparse, cfg, dense, mesh=mesh)
        if arch.arch_id == "deepfm":
            return R.deepfm_forward(params, sparse, cfg, mesh=mesh)
        return R.dcn_forward(params, sparse, cfg, dense, mesh=mesh)

    n_dense = getattr(cfg, "n_dense", 0)

    if spec.kind == "train":
        if is_b4r:
            b, s = sp["batch"], cfg.seq_len
            n_mask, n_neg = 16, 256

            def fn(params, opt_state, seq, mask_pos, mask_tgt, negs):
                def loss_fn(p):
                    h = R.bert4rec_forward(p, seq, cfg, mesh=mesh)
                    hm = jnp.take_along_axis(
                        h, mask_pos[..., None], axis=1)          # (B, M, E)
                    emb = p["item_embed"]
                    pos_e = jnp.take(emb, mask_tgt, axis=0)       # (B, M, E)
                    neg_e = jnp.take(emb, negs, axis=0)           # (B, N, E)
                    pos_s = jnp.sum(hm * pos_e, -1)               # (B, M)
                    neg_s = jnp.einsum("bme,bne->bmn", hm, neg_e)
                    # sampled softmax
                    alls = jnp.concatenate([pos_s[..., None], neg_s], -1)
                    return -jnp.mean(jax.nn.log_softmax(alls.astype(jnp.float32))[..., 0])
                loss, grads = jax.value_and_grad(loss_fn)(params)
                params, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
                return params, opt_state, loss

            opt_abs = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), p_abs)
            o_specs = {"mu": p_specs, "nu": p_specs, "count": P()}
            args = (p_abs, opt_abs, _sd((b, s), jnp.int32),
                    _sd((b, n_mask), jnp.int32), _sd((b, n_mask), jnp.int32),
                    _sd((b, n_neg), jnp.int32))
            in_sh = (_named(mesh, p_specs), _named(mesh, o_specs),
                     _named(mesh, bspec), _named(mesh, bspec),
                     _named(mesh, bspec), _named(mesh, bspec))
            return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None,
                            donate_argnums=(0, 1))

        b = sp["batch"]

        def fn(params, opt_state, sparse, dense, labels):
            def loss_fn(p):
                return R.bce_loss(ctr_forward(p, sparse, dense), labels)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
            return params, opt_state, loss

        opt_abs = jax.eval_shape(partial(adamw_init, cfg=opt_cfg), p_abs)
        o_specs = {"mu": p_specs, "nu": p_specs, "count": P()}
        args = (p_abs, opt_abs, _sd((b, cfg.n_sparse), jnp.int32),
                _sd((b, max(n_dense, 1)), jnp.float32), _sd((b,), jnp.float32))
        in_sh = (_named(mesh, p_specs), _named(mesh, o_specs), _named(mesh, bspec),
                 _named(mesh, bspec), _named(mesh, P(dp if dp else None)))
        return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None,
                        donate_argnums=(0, 1))

    if spec.kind == "serve":
        b = sp["batch"]
        if is_b4r:
            def fn(params, seq):
                h = R.bert4rec_forward(params, seq, cfg, mesh=mesh)
                user = h[:, -1]                                    # (B, E)
                # chunked top-k over the (sharded) item table
                chunk = max(1, min(1024, b))
                nb = b // chunk
                uc = user[: nb * chunk].reshape(nb, chunk, -1)

                def score_chunk(carry, u):
                    sc = u @ params["item_embed"][: cfg.n_items].T
                    v, i = jax.lax.top_k(sc, 100)
                    return carry, (v, i)

                _, (v, i) = jax.lax.scan(score_chunk, 0, uc)
                return v.reshape(nb * chunk, 100), i.reshape(nb * chunk, 100)

            args = (p_abs, _sd((b, cfg.seq_len), jnp.int32))
            in_sh = (_named(mesh, p_specs), _named(mesh, bspec))
            return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None)

        def fn(params, sparse, dense):
            return ctr_forward(params, sparse, dense)

        args = (p_abs, _sd((b, cfg.n_sparse), jnp.int32),
                _sd((b, max(n_dense, 1)), jnp.float32))
        in_sh = (_named(mesh, p_specs), _named(mesh, bspec), _named(mesh, bspec))
        return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None)

    # retrieval: 1 query vs n_candidates
    n_cand = sp["n_candidates"]
    if is_b4r:
        def fn(params, seq):
            h = R.bert4rec_forward(params, seq, cfg, mesh=mesh)
            user = h[0, -1]
            scores = params["item_embed"][: cfg.n_items] @ user
            return jax.lax.top_k(scores, 100)

        args = (p_abs, _sd((1, cfg.seq_len), jnp.int32))
        in_sh = (_named(mesh, p_specs), _named(mesh, P()))
        return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None)

    def fn(params, sparse, dense):
        scores = ctr_forward(params, sparse, dense)
        return jax.lax.top_k(scores, 100)

    cand_spec = P(dp if dp else None, None)
    args = (p_abs, _sd((n_cand, cfg.n_sparse), jnp.int32),
            _sd((n_cand, max(n_dense, 1)), jnp.float32))
    in_sh = (_named(mesh, p_specs), _named(mesh, cand_spec), _named(mesh, cand_spec))
    return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None)


# ================================================================= websearch
def _build_websearch(arch: ArchDef, shape_name: str, mesh, reduced: bool) -> CellSpec:
    from repro.core.environment import EnvConfig
    from repro.core.qlearning import QConfig, train_batch
    from repro.core.rollout import unified_rollout
    from repro.core.state_bins import StateBins
    from repro.core.match_rules import default_rule_library
    from repro.core.telescope import merge_shard_candidates
    from repro.index.builder import MAX_QUERY_TERMS
    from repro.index.corpus import N_FIELDS
    from repro.policies import TabularQPolicy

    wcfg = arch.model_cfg(reduced)
    spec = arch.shape(shape_name)
    sp = dict(REDUCED_SHAPES[spec.kind]) if reduced else dict(spec.params)
    q_batch = sp["query_batch"]
    dp = _dp(mesh)
    msize = mesh.shape["model"] if mesh else 1

    nb_local = wcfg.n_blocks // msize
    w = wcfg.block_docs // 32
    n_pad_local = nb_local * wcfg.block_docs
    env_cfg = EnvConfig(n_blocks=nb_local, block_docs=wcfg.block_docs,
                        k_rules=wcfg.k_rules, max_candidates=wcfg.max_candidates,
                        n_top=wcfg.n_top, u_budget=wcfg.u_budget)
    qcfg = QConfig(p=wcfg.p_bins, n_actions=env_cfg.n_actions, t_max=wcfg.t_max)
    ruleset = default_rule_library()
    pu = int(np.sqrt(wcfg.p_bins))
    pv = wcfg.p_bins // pu
    bins_abs = StateBins(u_edges=_sd((pu - 1,), jnp.float32),
                         v_edges=_sd((pu, pv - 1), jnp.float32))
    bins_specs = StateBins(u_edges=P(), v_edges=P())

    occ_abs = _sd((q_batch, wcfg.n_blocks, MAX_QUERY_TERMS, N_FIELDS, w), jnp.uint32)
    scores_abs = _sd((q_batch, wcfg.n_blocks * wcfg.block_docs), jnp.float32)
    tp_abs = _sd((q_batch, MAX_QUERY_TERMS), jnp.bool_)
    q_abs = _sd((wcfg.p_bins, env_cfg.n_actions), jnp.float32)

    occ_spec = P(dp if dp else None, "model" if mesh else None, None, None, None)
    scores_spec = P(dp if dp else None, "model" if mesh else None)
    tp_spec = P(dp if dp else None, None)

    if spec.kind == "serve_websearch":
        def local_serve(qt, bins, occ, scores, tp):
            final = unified_rollout(env_cfg, ruleset, bins, TabularQPolicy(qt),
                                    qcfg.t_max, occ, scores, tp,
                                    backend=wcfg.backend).final_state
            if mesh is None:
                return final.cand, final.u, final.cand_cnt
            shard = jax.lax.axis_index("model")
            cand = jnp.where(final.cand >= 0,
                             final.cand + shard * n_pad_local, -1)
            gathered = jax.lax.all_gather(cand, "model")        # (S, Qloc, K)
            merged = merge_shard_candidates(gathered, keep=wcfg.max_candidates)
            u_tot = jax.lax.psum(final.u, "model")
            # Candidates that survive the merge, as ServeEngine's
            # ShardedExecutor counts them.
            return merged, u_tot, jnp.sum(merged >= 0, axis=1, dtype=jnp.int32)

        if mesh is None:
            fn = local_serve
        else:
            fn = jax.shard_map(
                local_serve, mesh=mesh,
                in_specs=(P(), StateBins(u_edges=P(), v_edges=P()),
                          P(dp, "model", None, None, None),
                          P(dp, "model"), P(dp, None)),
                out_specs=(P(dp, None), P(dp), P(dp)),
                check_vma=False,
            )
        args = (q_abs, bins_abs, occ_abs, scores_abs, tp_abs)
        in_sh = (_named(mesh, P()), _named(mesh, bins_specs), _named(mesh, occ_spec),
                 _named(mesh, scores_spec), _named(mesh, tp_spec))
        return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None)

    # rl_rollout: a policy-training step; per-shard TD stats are averaged
    # over the index shards ("the same policy on every machine").
    lp = wcfg.t_max
    prod_abs = _sd((q_batch, lp), jnp.float32)

    def local_train(qt, bins, occ, scores, tp, prod_r, rng):
        q_new, metrics = train_batch(env_cfg, qcfg, ruleset, bins, qt, occ,
                                     scores, tp, prod_r, jnp.float32(0.1), rng,
                                     backend=wcfg.backend)
        if mesh is not None:
            q_new = jax.lax.pmean(q_new, "model")
            q_new = jax.lax.pmean(q_new, dp)
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(jax.lax.pmean(m, "model"), dp), metrics)
        return q_new, metrics

    if mesh is None:
        fn = lambda qt, bins, occ, scores, tp, prod_r, rng: local_train(
            qt, bins, occ, scores, tp, prod_r, rng)
    else:
        fn = jax.shard_map(
            local_train, mesh=mesh,
            in_specs=(P(), StateBins(u_edges=P(), v_edges=P()),
                      P(dp, "model", None, None, None), P(dp, "model"),
                      P(dp, None), P(dp, None), P()),
            out_specs=(P(), jax.tree_util.tree_map(lambda _: P(),
                       {"mean_u": 0, "mean_v": 0, "mean_cand": 0,
                        "mean_reward": 0, "q_abs_mean": 0})),
            check_vma=False,
        )
    rng_abs = jax.eval_shape(lambda: jax.random.key(0))
    args = (q_abs, bins_abs, occ_abs, scores_abs, tp_abs, prod_abs, rng_abs)
    in_sh = (_named(mesh, P()), _named(mesh, bins_specs), _named(mesh, occ_spec),
             _named(mesh, scores_spec), _named(mesh, tp_spec),
             _named(mesh, P(dp if dp else None, None)), _named(mesh, P()))
    return CellSpec(arch.arch_id, shape_name, fn, args, in_sh, None)


# =================================================================== dispatch
def build_cell(arch_id: str, shape_name: str, mesh=None, reduced: bool = False,
               cfg_override=None) -> CellSpec:
    arch = get_arch(arch_id)
    if cfg_override is not None:
        arch = dataclasses.replace(arch, model_cfg=lambda reduced_: cfg_override)
    builder = {
        "lm": _build_lm,
        "gnn": _build_gnn,
        "recsys": _build_recsys,
        "websearch": _build_websearch,
    }[arch.family]
    return builder(arch, shape_name, mesh, reduced)
