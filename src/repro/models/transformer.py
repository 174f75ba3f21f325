"""Decoder-only transformer LM family (dense GQA / MoE / MLA).

Covers mistral-nemo-12b, starcoder2-3b, phi4-mini-3.8b,
deepseek-v2-lite-16b and grok-1-314b from one config surface.

Compile-time discipline (one CPU core compiles 80 dry-run cells):
- `lax.scan` over layers with stacked parameters — HLO size is O(1) in
  depth.
- optional `jax.checkpoint` (full remat) around the layer body.
- chunked causal attention (models/attention.py) and a chunked
  softmax-xent so no (tokens × vocab) or (S × S) tensor is ever
  materialized whole.

Decode carries a stacked KV cache pytree (L leading dim); MLA caches
the compressed (c_kv, k_rope) pair only.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .attention import (
    AttnConfig, MLAConfig, gqa_decode, gqa_forward, gqa_init, mla_decode,
    mla_forward, mla_init,
)
from .layers import dense_init, mlp_apply, mlp_init, rms_norm
from .moe import MoEConfig, moe_ffn, moe_ffn_sharded, moe_init

__all__ = ["TransformerConfig", "init_params", "forward", "lm_loss", "prefill",
           "decode_step", "init_kv_cache"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    mlp_kind: str = "swiglu"          # swiglu | gelu
    attn_kind: str = "gqa"            # gqa | mla
    moe: Optional[MoEConfig] = None   # None = dense FFN
    mla: Optional[MLAConfig] = None
    rope_theta: float = 10000.0
    max_seq: int = 4096
    q_chunk: int = 512
    loss_chunk: int = 2048
    remat: bool = True
    param_dtype: Any = jnp.float32
    use_flash: bool = False           # Pallas kernels on TPU
    sp_carry: bool = True             # Megatron-SP: shard residuals over `model`
    microbatch: int = 1               # gradient-accumulation microbatches
    fsdp: bool = False                # also shard expert weights over `data`
                                      # (grok-1: params don't fit TP-only)
    grad_accum_dtype: Any = jnp.float32   # bf16 halves accumulation HBM
    zero3: bool = False               # dense layers: weights fully sharded,
                                      # gathered per layer; activations local
                                      # (no TP collectives) — §Perf hillclimb #1

    def attn_cfg(self) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv=self.n_kv,
            d_head=self.d_head, rope_theta=self.rope_theta,
            q_chunk=self.q_chunk, use_flash=self.use_flash,
        )


# ---------------------------------------------------------------- params
def _layer_init(rng, cfg: TransformerConfig) -> Dict:
    k_attn, k_ffn = jax.random.split(rng)
    dt = cfg.param_dtype
    if cfg.attn_kind == "mla":
        attn = mla_init(k_attn, cfg.mla, dtype=dt)
    else:
        attn = gqa_init(k_attn, cfg.attn_cfg(), dtype=dt)
    if cfg.moe is not None:
        ffn = moe_init(k_ffn, cfg.moe, dtype=dt)
    else:
        ffn = mlp_init(k_ffn, cfg.d_model, cfg.d_ff, cfg.mlp_kind, dtype=dt)
    return {
        "attn": attn,
        "ffn": ffn,
        "ln1": jnp.ones((cfg.d_model,), dt),
        "ln2": jnp.ones((cfg.d_model,), dt),
    }


def init_params(rng, cfg: TransformerConfig) -> Dict:
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    # Stacked layers: every leaf gets a leading (n_layers,) dim for lax.scan.
    layers = jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys)
    return {
        "embed": dense_init(k_emb, (cfg.vocab, cfg.d_model), scale=0.02,
                            dtype=cfg.param_dtype),
        "layers": layers,
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "lm_head": dense_init(k_head, (cfg.d_model, cfg.vocab), dtype=cfg.param_dtype),
    }


# --------------------------------------------------------------- forward
def _layer_fwd_zero3(cfg: TransformerConfig, mesh, lp: Dict, x: jnp.ndarray):
    """ZeRO-3 dense block: weights stored P(data, model)-sharded, gathered
    HERE (inside the remat region → re-gathered in bwd), all math local
    over the batch shard — zero activation collectives."""
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def gather2d(w):
        w = jax.lax.all_gather(w, "model", axis=1, tiled=True)
        for ax_name in reversed(dp):
            w = jax.lax.all_gather(w, ax_name, axis=0, tiled=True)
        return w

    def local_fn(lp_local, x_local):
        full = {
            "attn": {k: gather2d(v) for k, v in lp_local["attn"].items()},
            "ffn": {k: (gather2d(v) if v.ndim == 2 else
                        jax.lax.all_gather(v, "model", axis=0, tiled=True))
                    for k, v in lp_local["ffn"].items()},
            "ln1": lp_local["ln1"], "ln2": lp_local["ln2"],
        }
        h = rms_norm(x_local, full["ln1"])
        h = gqa_forward(full["attn"], h, cfg.attn_cfg())
        x2 = x_local + h
        h = rms_norm(x2, full["ln2"])
        h = mlp_apply(full["ffn"], h, cfg.mlp_kind)
        return x2 + h

    w2d = P(dp, "model")
    w1d = P("model")
    lp_specs = {
        "attn": {k: w2d for k in lp["attn"]},
        "ffn": {k: (w2d if lp["ffn"][k].ndim == 2 else w1d) for k in lp["ffn"]},
        "ln1": P(), "ln2": P(),
    }
    # 256-way DP: batch shards over data AND model axes (weights are the
    # only thing living on the model axis in zero3 mode).  Falls back to
    # data-only batch sharding when the (micro)batch is too small —
    # zero3 therefore pairs with microbatch=1.
    import numpy as _np
    bx = x.shape[0]
    axes = dp + ("model",)
    n_ax = int(_np.prod([mesh.shape[a] for a in axes]))
    if bx % n_ax != 0 or bx < n_ax:
        axes = dp
    xspec = P(axes, None, None)
    out = jax.shard_map(local_fn, mesh=mesh, in_specs=(lp_specs, xspec),
                        out_specs=xspec, check_vma=False)(lp, x)
    return out, jnp.float32(0.0)


def _layer_fwd(cfg: TransformerConfig, mesh, lp: Dict, x: jnp.ndarray):
    """One block: pre-norm attn + pre-norm FFN. x: (B, S, d)."""
    if getattr(cfg, "zero3", False) and mesh is not None and cfg.moe is None:
        return _layer_fwd_zero3(cfg, mesh, lp, x)
    h = rms_norm(x, lp["ln1"])
    if cfg.attn_kind == "mla":
        h = mla_forward(lp["attn"], h, cfg.mla)
    else:
        h = gqa_forward(lp["attn"], h, cfg.attn_cfg())
    x = x + h

    h = rms_norm(x, lp["ln2"])
    if cfg.moe is not None:
        b, s, d = h.shape
        out, aux = _apply_moe_ffn(lp["ffn"], h.reshape(b * s, d), cfg, mesh)
        h = out.reshape(b, s, d)
    else:
        h = mlp_apply(lp["ffn"], h, cfg.mlp_kind)
        aux = jnp.float32(0.0)
    out = x + h
    if mesh is not None and cfg.sp_carry and out.shape[1] % mesh.shape["model"] == 0:
        # Megatron sequence parallelism: the saved residual (the scan
        # carry — the dominant activation-memory term under remat) shards
        # its sequence dim over `model`; XLA all-gathers at QKV and
        # reduce-scatters after the FFN.  3.1x activation memory saving
        # measured on starcoder2 train_4k (EXPERIMENTS.md §Perf).
        from jax.sharding import NamedSharding, PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(dp if dp else None, "model", None)))
    return out, aux


def forward(params: Dict, tokens: jnp.ndarray, cfg: TransformerConfig,
            mesh=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens (B, S) -> (final hidden (B, S, d), aux_loss)."""
    x = jnp.take(params["embed"], tokens, axis=0)

    body = partial(_layer_fwd, cfg, mesh)
    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(x, lp):
        x, aux = body(lp, x)
        return x, aux

    x, auxes = lax.scan(scan_body, x, params["layers"])
    return rms_norm(x, params["ln_f"]), jnp.sum(auxes)


def lm_loss(params: Dict, tokens: jnp.ndarray, targets: jnp.ndarray,
            cfg: TransformerConfig, mesh=None) -> jnp.ndarray:
    """Next-token CE, chunked over tokens so the (chunk, vocab) logits
    tile stays bounded (vocab up to 200K)."""
    h, aux = forward(params, tokens, cfg, mesh)
    b, s, d = h.shape
    flat_h = h.reshape(b * s, d)
    flat_t = targets.reshape(b * s)

    chunk = min(cfg.loss_chunk, b * s)
    n_chunks = (b * s) // chunk
    hc = flat_h[: n_chunks * chunk].reshape(n_chunks, chunk, d)
    tc = flat_t[: n_chunks * chunk].reshape(n_chunks, chunk)
    if getattr(cfg, "zero3", False) and mesh is not None:
        # zero3 replicates lm_head; shard the loss chunk rows over ALL
        # axes so the (chunk, vocab) logits tile stays per-device-small
        from jax.sharding import NamedSharding, PartitionSpec as P
        import numpy as _np
        axes = tuple(a for a in ("pod", "data") if a in mesh.shape) + ("model",)
        n_ax = int(_np.prod([mesh.shape[a] for a in axes]))
        if chunk % n_ax == 0:
            hc = jax.lax.with_sharding_constraint(
                hc, NamedSharding(mesh, P(None, axes, None)))
            tc = jax.lax.with_sharding_constraint(
                tc, NamedSharding(mesh, P(None, axes)))

    def chunk_loss(carry, xs):
        hx, t = xs
        logits = (hx @ params["lm_head"]).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t[:, None], axis=1)[:, 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = lax.scan(chunk_loss, jnp.float32(0.0), (hc, tc))
    loss = total / (n_chunks * chunk)
    return loss + 0.01 * aux


# ----------------------------------------------------------------- decode
def init_kv_cache(cfg: TransformerConfig, batch: int, max_seq: int,
                  dtype=None) -> Dict:
    dt = dtype or cfg.param_dtype
    if cfg.attn_kind == "mla":
        return {
            "c": jnp.zeros((cfg.n_layers, batch, max_seq, cfg.mla.kv_lora_rank), dt),
            "k_rope": jnp.zeros((cfg.n_layers, batch, max_seq, cfg.mla.d_rope), dt),
        }
    return {
        "k": jnp.zeros((cfg.n_layers, batch, max_seq, cfg.n_kv, cfg.d_head), dt),
        "v": jnp.zeros((cfg.n_layers, batch, max_seq, cfg.n_kv, cfg.d_head), dt),
    }


def _apply_moe_ffn(ffn_params, flat: jnp.ndarray, cfg: TransformerConfig, mesh):
    """MoE FFN on (T, d) tokens; shard_map path when a mesh is given.
    Tokens shard over the data axes when divisible, else replicate
    (B=1 long-context decode)."""
    if mesh is None:
        return moe_ffn(ffn_params, flat, cfg.moe)
    import numpy as _np
    da = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_size = int(_np.prod([mesh.shape[a] for a in da])) if da else 1
    if flat.shape[0] % dp_size != 0 or flat.shape[0] < dp_size:
        da = ()
    return moe_ffn_sharded(ffn_params, flat, cfg.moe, mesh, data_axes=da,
                           fsdp=getattr(cfg, "fsdp", False))


def prefill(params: Dict, tokens: jnp.ndarray, cfg: TransformerConfig,
            mesh=None) -> Tuple[jnp.ndarray, Dict]:
    """Run the prompt, returning last-position logits and the KV cache.
    (Cache layout matches init_kv_cache; prompt occupies positions
    [0, S).)"""
    x = jnp.take(params["embed"], tokens, axis=0)

    def scan_body(x, lp):
        h = rms_norm(x, lp["ln1"])
        if cfg.attn_kind == "mla":
            h, cache = mla_forward(lp["attn"], h, cfg.mla, return_cache=True)
        else:
            h, cache = gqa_forward(lp["attn"], h, cfg.attn_cfg(), return_cache=True)
        x = x + h
        h2 = rms_norm(x, lp["ln2"])
        if cfg.moe is not None:
            b, s, d = h2.shape
            out, _ = _apply_moe_ffn(lp["ffn"], h2.reshape(b * s, d), cfg, mesh)
            h2 = out.reshape(b, s, d)
        else:
            h2 = mlp_apply(lp["ffn"], h2, cfg.mlp_kind)
        return x + h2, cache

    x, caches = lax.scan(scan_body, x, params["layers"])
    h_last = rms_norm(x[:, -1], params["ln_f"])
    logits = (h_last @ params["lm_head"]).astype(jnp.float32)
    return logits, caches


def decode_step(params: Dict, token: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
                cfg: TransformerConfig, mesh=None) -> Tuple[jnp.ndarray, Dict]:
    """One decode step. token (B,) int32; pos (B,) current lengths.
    Returns (logits (B, vocab) f32, new cache)."""
    x = jnp.take(params["embed"], token, axis=0)                  # (B, d)

    def scan_body(x, xs):
        lp, layer_cache = xs
        h = rms_norm(x, lp["ln1"])
        if cfg.attn_kind == "mla":
            h, new_cache = mla_decode(lp["attn"], h, layer_cache, pos, cfg.mla)
        else:
            h, new_cache = gqa_decode(lp["attn"], h, layer_cache, pos, cfg.attn_cfg())
        x = x + h
        h2 = rms_norm(x, lp["ln2"])
        if cfg.moe is not None:
            out, _ = _apply_moe_ffn(lp["ffn"], h2, cfg, mesh)
            h2 = out
        else:
            h2 = mlp_apply(lp["ffn"], h2, cfg.mlp_kind)
        return x + h2, new_cache

    x, new_cache = lax.scan(scan_body, x, (params["layers"], cache))
    h = rms_norm(x, params["ln_f"])
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    return logits, new_cache
