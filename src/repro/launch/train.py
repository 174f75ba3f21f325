"""End-to-end training drivers.

Two modes, matching the paper's kind (candidate-generation serving) and
the framework's generality:

  policy  — the paper: build corpus/index/query log, train the L1
            ranker, fit state bins, Q-learn per-category match policies,
            evaluate vs production plans.  Fault-tolerant: checkpoints
            the Q-table + RNG state every N iters and resumes.

  lm      — train a reduced LM config for a few hundred steps on
            synthetic data through the exact sharded train step the
            dry-run lowers (1-device mesh on CPU), with checkpoint/
            restart via the resilient loop.

    PYTHONPATH=src python -m repro.launch.train policy --iters 200
    PYTHONPATH=src python -m repro.launch.train lm --arch starcoder2-3b --steps 100
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np


def train_policy_cmd(args) -> None:
    from repro.data.querylog import CAT1, CAT2, QueryLogConfig
    from repro.distributed.checkpoint import CheckpointManager
    from repro.index.corpus import CorpusConfig
    from repro.policies import PolicyStore, TabularQPolicy
    from repro.ranking.metrics import relative_delta
    from repro.system import RetrievalSystem, SystemConfig

    sys_ = RetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=args.n_docs, vocab_size=args.vocab, seed=0),
        querylog=QueryLogConfig(n_queries=args.n_queries, seed=0),
        block_docs=args.block_docs, p_bins=args.p_bins,
        u_budget=args.u_budget, l1_steps=300,
        backend=args.backend,
    ))
    print(f"[build] {sys_.index.n_docs} docs, {sys_.log.n_queries} queries, "
          f"{sys_.index.n_blocks} blocks ({sys_.build_time:.1f}s)")
    sys_.fit_l1(n_queries=min(192, args.n_queries // 4))
    sys_.fit_state_bins(n_queries=128)
    print(f"[bins] p={sys_.bins.p}")

    # Trained policies are published per category into a PolicyStore —
    # a serving engine subscribed to this store would hot-swap to each
    # new version (the serve-while-training loop, docs/policies.md).
    # Every snapshot must cover every category, so not-yet-trained ones
    # serve the hand-tuned static plan.
    store = PolicyStore(staleness_bound=1)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    out = {}
    trained = sys_.baseline_policies((CAT1, CAT2))
    for cat, name in ((CAT1, "CAT1"), (CAT2, "CAT2")):
        q, hist = sys_.train_policy(cat, iters=args.iters, batch=args.batch,
                                    log_every=max(args.iters // 8, 1))
        mgr.save(cat, {"q": q})
        trained[cat] = TabularQPolicy(q)
        version = store.publish(dict(trained))
        qids = np.where(sys_.log.category == cat)[0][:256]
        res = sys_.evaluate(q, qids, cat)
        out[name] = {
            "delta_u_pct": relative_delta(res["policy_u"], res["baseline_u"]),
            "delta_ncg_pct": relative_delta(res["policy_ncg"], res["baseline_ncg"]),
            "policy_version": version,
        }
        print(f"[{name}] Δu={out[name]['delta_u_pct']:+.1f}%  "
              f"ΔNCG={out[name]['delta_ncg_pct']:+.1f}%  "
              f"(published policy snapshot v{version})")
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))


def train_lm_cmd(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.distributed.fault_tolerance import (
        FailureInjector, FaultToleranceConfig, run_resilient_loop,
    )
    from repro.launch.steps import build_cell

    cell = build_cell(args.arch, "train_4k", mesh=None, reduced=True)
    rng = np.random.default_rng(0)

    params, opt_state = cell.args[0], cell.args[1]
    def mk(x):
        if hasattr(x, "dtype") and not isinstance(x, jnp.ndarray):
            if jnp.issubdtype(x.dtype, jnp.integer):
                return jnp.zeros(x.shape, x.dtype)
            return jnp.zeros(x.shape, x.dtype)
        return x
    # real init (not zeros) for params
    from repro.configs import get_arch
    from repro.models.transformer import init_params
    cfg = get_arch(args.arch).model_cfg(True)
    params = init_params(jax.random.key(0), cfg)
    opt_state = jax.tree_util.tree_map(mk, opt_state)

    b, s = cell.args[2].shape
    step_jit = jax.jit(cell.fn, donate_argnums=(0, 1))
    losses = []

    def data_for(step: int):
        r = np.random.default_rng(1234 + step)        # stateless, seeded by step
        toks = r.integers(0, cfg.vocab, size=(b, s + 1))
        return (jnp.asarray(toks[:, :-1], jnp.int32),
                jnp.asarray(toks[:, 1:], jnp.int32))

    def step_fn(state, step):
        p, o = state["params"], state["opt"]
        tokens, targets = data_for(step)
        p, o, metrics = step_jit(p, o, tokens, targets)
        losses.append(float(metrics["loss"]))
        if step % 10 == 0:
            print(f"step {step:4d} loss {losses[-1]:.4f}")
        return {"params": p, "opt": o}

    ft = FaultToleranceConfig(ckpt_dir=args.ckpt_dir, ckpt_every=25,
                              async_save=True)
    injector = FailureInjector(fail_at=(args.steps // 2,)) if args.inject_failure else None
    res = run_resilient_loop({"params": params, "opt": opt_state}, step_fn,
                             args.steps, ft, injector=injector)
    print(f"[done] steps={args.steps} restarts={res['restarts']} "
          f"first_loss={losses[0]:.3f} last_loss={losses[-1]:.3f} "
          f"wall={res['wall_s']:.0f}s")
    assert losses[-1] < losses[0], "loss should decrease"


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    p = sub.add_parser("policy")
    p.add_argument("--n-docs", type=int, default=8192)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--n-queries", type=int, default=2000)
    p.add_argument("--block-docs", type=int, default=256)
    p.add_argument("--p-bins", type=int, default=1024)
    p.add_argument("--u-budget", type=int, default=1024)
    p.add_argument("--iters", type=int, default=200)
    p.add_argument("--batch", type=int, default=48)
    p.add_argument("--ckpt-dir", default="results/ckpt_policy")
    p.add_argument("--out", default="results/train_policy.json")
    p.add_argument("--backend", default="xla",
                   help="index-scan backend for training/eval rollouts "
                        "(see repro.core.scan_backends.available_backends)")
    p.set_defaults(fn=train_policy_cmd)

    p = sub.add_parser("lm")
    p.add_argument("--arch", default="starcoder2-3b")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--ckpt-dir", default="results/ckpt_lm")
    p.add_argument("--inject-failure", action="store_true")
    p.set_defaults(fn=train_lm_cmd)

    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    main()
