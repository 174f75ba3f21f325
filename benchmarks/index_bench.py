"""Tiered live index at scale (docs/index.md): build / ingest / merge
throughput at >= 1M docs, plus bytes-streamed-per-query priced per scan
backend on a live (base + delta) serving system.

Two stages:

* **Scale** — a >= 1M-doc index is synthesized as flat (doc, term) pair
  soup (vectorized Zipf draws, never per-doc Python lists) and fed to
  ``build_index_from_pairs``; then a ``LiveIndex`` over it absorbs a
  stream of appended documents through commit epochs and one timed
  background-style merge into a new mmapped base generation.  Metrics:
  build docs/s and pairs/s, ingest docs/s, merge wall-time.
* **Serving** — a small ``LiveRetrievalSystem`` runs the freshness
  workload (adds + chase queries + a merge), then one xla rollout
  prices every backend's byte model over a mixed wave using
  ``benchmarks.serve_bench``'s per-lane accounting: "xla" streams the
  full T·F·W tile per scanned block, the plane-pruned Pallas backend
  streams only active planes rounded to its speculation chunk — the
  paper's bandwidth story (bytes ∝ u, not index size) measured on a
  base+delta view instead of a static index.

Results land in ``results/index_bench.json`` via the shared recorder::

    PYTHONPATH=src python -m benchmarks.index_bench            # 1M docs
    PYTHONPATH=src python -m benchmarks.index_bench --fast     # CI-sized
    PYTHONPATH=src python -m benchmarks.run --index-bench
"""
from __future__ import annotations

import argparse
import tempfile
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

# Mean sorted-unique terms per doc per field, in the corpus generator's
# (anchor, url, body, title) proportions.
FIELD_TERMS = (1, 2, 24, 4)


# ------------------------------------------------------------ synthesis
def zipf_p(vocab_size: int, a: float = 1.1) -> np.ndarray:
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -a
    return p / p.sum()


def synth_pairs(n_docs: int, vocab_size: int,
                rng: np.random.Generator) -> Tuple[List[np.ndarray],
                                                   List[np.ndarray]]:
    """Flat per-field (doc, term) pair soup for ``n_docs`` documents —
    one vectorized Zipf draw per field, no per-doc lists.  Duplicate
    (doc, term) pairs are left in; the builder's dedup path canonizes
    them (that path is exactly what the live merge compaction uses)."""
    p = zipf_p(vocab_size)
    pair_docs, pair_terms = [], []
    for k in FIELD_TERMS:
        pair_docs.append(np.repeat(np.arange(n_docs, dtype=np.int64), k))
        pair_terms.append(rng.choice(
            vocab_size, size=n_docs * k, p=p).astype(np.int32))
    return pair_docs, pair_terms


def synth_docs(n: int, vocab_size: int,
               rng: np.random.Generator) -> List[List[np.ndarray]]:
    """Per-doc field lists for the ingest stage (the add_document API
    takes documents, not pair soup)."""
    p = zipf_p(vocab_size)
    docs = []
    for _ in range(n):
        fields = [np.unique(rng.choice(vocab_size, size=max(1, k), p=p))
                  .astype(np.int32) for k in FIELD_TERMS]
        docs.append(fields)
    return docs


# ---------------------------------------------------------- scale stage
def bench_scale(n_docs: int, vocab_size: int, block_docs: int,
                n_add: int, docs_per_commit: int, seed: int = 0) -> dict:
    from repro.index.builder import build_index_from_pairs
    from repro.index.live import LiveIndex

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    pair_docs, pair_terms = synth_pairs(n_docs, vocab_size, rng)
    synth_s = time.perf_counter() - t0
    n_pairs = int(sum(len(t) for t in pair_terms))

    t0 = time.perf_counter()
    index = build_index_from_pairs(
        pair_docs, pair_terms, n_docs=n_docs, vocab_size=vocab_size,
        static_rank=rng.random(n_docs).astype(np.float32),
        block_docs=block_docs, dedup=True)
    build_s = time.perf_counter() - t0
    print(f"index_build_{n_docs}d,{build_s*1e6:.0f},"
          f"{n_docs/build_s:.0f}docs_per_s,{n_pairs/build_s:.2e}pairs_per_s"
          f" (synth {synth_s:.1f}s)")

    with tempfile.TemporaryDirectory(prefix="index-bench-") as tmp:
        live = LiveIndex(index, storage_dir=tmp)
        docs = synth_docs(n_add, vocab_size, rng)
        t0 = time.perf_counter()
        for i in range(0, n_add, docs_per_commit):
            live.add_documents(docs[i: i + docs_per_commit])
            live.commit()
        ingest_s = time.perf_counter() - t0
        print(f"index_ingest_{n_add}d,{ingest_s*1e6:.0f},"
              f"{n_add/ingest_s:.0f}docs_per_s,"
              f"{live.epoch - 1}epochs")

        t0 = time.perf_counter()
        live.merge()
        merge_s = time.perf_counter() - t0
        st = live.stats()
        assert st["delta_docs"] == 0 and st["generation"] == 1
        assert st["base_mmapped"], "merged generation must be mmapped"
        print(f"index_merge_{st['n_docs']}d,{merge_s*1e6:.0f},"
              f"{st['n_docs']/merge_s:.0f}docs_per_s,gen{st['generation']}")

    return {
        "n_docs": n_docs, "n_pairs": n_pairs,
        "synth_s": synth_s, "build_s": build_s,
        "build_docs_per_s": n_docs / build_s,
        "build_pairs_per_s": n_pairs / build_s,
        "ingest_docs": n_add, "ingest_s": ingest_s,
        "ingest_docs_per_s": n_add / ingest_s,
        "merge_s": merge_s,
        "merge_docs_per_s": (n_docs + n_add) / merge_s,
    }


# -------------------------------------------------------- serving stage
def _depth_scaled_policies(sys_, view):
    """Depth-rate the production plans for a deep index: a Δu quota is
    a scan-length rating, and a plan hand-tuned on a 16-block dev index
    would stop a 2000-block scan after touching a fraction of a permille
    of the posting planes.  Quotas scale with the block-count ratio;
    the env's ``u_budget`` (unchanged) becomes the binding constraint —
    exactly the paper's regime, where bytes ∝ u for the pruned backend
    no matter how deep the index gets."""
    import jax.numpy as jnp

    from repro.core.match_plan import MatchPlan
    from repro.data.querylog import CAT1, CAT2
    from repro.policies import StaticPlanPolicy

    factor = max(1, round(view.capacity_blocks / sys_.env_cfg.n_blocks))
    out = {}
    for cat in (CAT1, CAT2):
        p = sys_.plan_for_category(cat)
        plan = MatchPlan(
            rule_idx=p.rule_idx, reset_before=p.reset_before,
            du_quota=(p.du_quota * factor).astype(jnp.int32),
            dv_quota=(p.dv_quota * factor).astype(jnp.int32))
        out[cat] = StaticPlanPolicy(plan, sys_.env_cfg.n_actions)
    return out, factor


def _deep_pricing(sys_, policies, qids, view, chunk_q: int = 8):
    """Per-lane scan pricing (serve_bench's accounting) for rollouts
    against a DEEP live view: occupancy and score planes come from
    ``view``; plans, ruleset, bins and L1 params from ``sys_`` (they
    are depth-independent).  Returns a ``scan_pricing``-shaped result
    for :func:`benchmarks.serve_bench.bytes_streamed_per_query`."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.rollout import unified_rollout
    from repro.data.querylog import CAT1, CAT2
    from repro.ranking.l1_ranker import idf_for_terms, score_all_docs

    qids = np.asarray(qids)
    log = sys_.log
    env = dataclasses.replace(sys_.env_cfg, n_blocks=view.capacity_blocks)
    allowed = np.asarray(sys_.ruleset.allowed)
    k = allowed.shape[0]

    # Capacity-padded score planes for the deep view (the live
    # system's _epoch_planes formula at this depth).
    cap = view.capacity_docs
    sr = np.zeros(cap, np.float32)
    sr[: view.n_docs] = view.static_rank()
    dl_raw = view.doc_len()
    dl = np.zeros((cap, dl_raw.shape[1]), np.float32)
    dl[: view.n_docs] = np.log1p(dl_raw) / np.log(256.0)
    sr, dl = jnp.asarray(sr), jnp.asarray(dl)
    df_body = np.asarray(view.df[:, 2], dtype=np.float64)

    out = []
    for cat in (CAT1, CAT2):
        m = np.flatnonzero(log.category[qids] == cat)
        if not m.size:
            continue
        blocks_c, active_c = [], []
        # Fixed-size query chunks (tail padded by repetition) keep the
        # deep occupancy residency bounded and the rollout single-shape.
        for lo in range(0, m.size, chunk_q):
            sel = m[lo: lo + chunk_q]
            pad = np.concatenate([sel, np.repeat(sel[-1],
                                                 chunk_q - sel.size)])
            qs = qids[pad]
            term_lists = [log.terms[q, : log.n_terms[q]] for q in qs]
            occ = jnp.asarray(view.batch_query_occupancy(term_lists))
            tp = jnp.asarray(log.terms[qs] >= 0)
            idf = jnp.asarray(idf_for_terms(df_body, view.n_docs,
                                            log.terms[qs]))
            scores = jax.vmap(
                lambda o, i, t: score_all_docs(
                    sys_.l1_params, o, i, t, sr, dl))(occ, idf, tp)
            res = unified_rollout(env, sys_.ruleset, sys_.bins,
                                  policies[cat], sys_.qcfg.t_max,
                                  occ, scores, tp)
            a = np.asarray(res.transitions["a"])[:, : sel.size]
            u = np.asarray(res.trajectory["u"])[:, : sel.size]
            du = np.diff(u, axis=0, prepend=0)
            tpn = np.asarray(tp)[: sel.size]
            rule = np.clip(a, 0, k - 1)
            n_active = (allowed[rule]
                        & tpn[None, :, :, None]).sum(axis=(2, 3))
            blocks_c.append(np.where(n_active > 0,
                                     du // np.maximum(n_active, 1), 0))
            active_c.append(n_active)
        out.append((m, np.concatenate(blocks_c, axis=1),
                    np.concatenate(active_c, axis=1)))
    return qids, out


def bench_serving(n_docs: int, deep_docs: int, n_queries: int, wave: int,
                  seed: int = 0) -> dict:
    """Bytes-per-query per scan backend on live base+delta views, at
    two depths: the small serving corpus and a >= 1M-doc deep index.
    The small `LiveRetrievalSystem` runs the freshness workload (adds +
    chase queries + one merge) and supplies plans/ruleset/L1 params;
    the deep stage rebuilds its corpus-shaped pair soup at full depth,
    adds a committed delta on top, and reprices the same wave there.
    One xla rollout prices every backend (they are bit-identical);
    the paper's bytes-∝-u advantage of the plane-pruned backend only
    emerges at depth, where per-step block counts dwarf the Pallas
    speculation chunk."""
    from benchmarks.serve_bench import bytes_streamed_per_query, scan_pricing
    from repro.core.scan_backends import DEFAULT_CHUNK_BLOCKS
    from repro.data.freshness import FreshnessConfig, FreshnessWorkload
    from repro.data.querylog import QueryLogConfig
    from repro.index.builder import build_index_from_pairs
    from repro.index.corpus import CorpusConfig
    from repro.index.live import LiveIndex, LiveRetrievalSystem
    from repro.system import SystemConfig

    block_docs, vocab = 512, 8192
    sys_ = LiveRetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=n_docs, vocab_size=vocab, seed=seed),
        querylog=QueryLogConfig(n_queries=n_queries, seed=seed),
        block_docs=block_docs, p_bins=512, u_budget=1024, l1_steps=80,
    ))
    sys_.fit_l1(n_queries=96)
    sys_.fit_state_bins(n_queries=64)
    policies = sys_.baseline_policies()

    workload = FreshnessWorkload(sys_, FreshnessConfig(
        docs_per_tick=32, wave_queries=wave, seed=seed))
    workload.tick()
    sys_.merge_index()          # a merged generation + residual delta
    qids = workload.tick()      # a mixed fresh + background wave

    out = {"serve": {}, "deep": {}}
    pricing = scan_pricing(sys_, policies, qids)
    for backend in ("xla", "pallas_block_scan"):
        out["serve"][backend] = bytes_streamed_per_query(
            pricing, sys_, backend, chunk=DEFAULT_CHUNK_BLOCKS)
        print(f"index_bytes_per_query_{backend}_{n_docs}d,"
              f"{out['serve'][backend]:.0f},"
              f"{sys_.index_epoch}epochs_live")

    # Deep stage: same vocab/block size as the serving system so its
    # query log and plans transfer; base pairs at full depth + a
    # committed delta so pricing runs against base+delta, not a static
    # index.
    rng = np.random.default_rng(seed + 1)
    pair_docs, pair_terms = synth_pairs(deep_docs, vocab, rng)
    deep_index = build_index_from_pairs(
        pair_docs, pair_terms, n_docs=deep_docs, vocab_size=vocab,
        static_rank=rng.random(deep_docs).astype(np.float32),
        block_docs=block_docs, dedup=True)
    cap = (deep_docs + block_docs - 1) // block_docs * block_docs
    deep = LiveIndex(deep_index, capacity_docs=cap + block_docs)
    deep.add_documents(synth_docs(64, vocab, rng))
    deep.commit()
    view = deep.store.snapshot().view

    import dataclasses
    import types
    deep_policies, quota_factor = _depth_scaled_policies(sys_, view)
    deep_pricing = _deep_pricing(sys_, deep_policies, qids, view)
    # bytes_streamed_per_query only touches env_cfg + ruleset: hand it
    # the deep-depth env without dragging a full system along.
    shim = types.SimpleNamespace(
        env_cfg=dataclasses.replace(sys_.env_cfg,
                                    n_blocks=view.capacity_blocks),
        ruleset=sys_.ruleset)
    for backend in ("xla", "pallas_block_scan"):
        out["deep"][backend] = bytes_streamed_per_query(
            deep_pricing, shim, backend, chunk=DEFAULT_CHUNK_BLOCKS)
        print(f"index_bytes_per_query_{backend}_{deep_docs}d,"
              f"{out['deep'][backend]:.0f},"
              f"{view.capacity_blocks}blocks,{deep.delta_docs}delta_docs,"
              f"quota_x{quota_factor}")

    r_serve = out["serve"]["xla"] / max(out["serve"]["pallas_block_scan"], 1.0)
    r_deep = out["deep"]["xla"] / max(out["deep"]["pallas_block_scan"], 1.0)
    print(f"index_bytes_ratio_xla_over_pallas,{r_serve:.2f}@{n_docs}d,"
          f"{r_deep:.2f}@{deep_docs}d")
    return {
        "serve_docs": n_docs, "deep_docs": deep_docs,
        "serve_queries": int(len(qids)),
        "index_epoch": sys_.index_epoch,
        "generation": sys_.live.generation,
        "delta_docs": sys_.live.delta_docs,
        "deep_delta_docs": deep.delta_docs,
        "deep_blocks": view.capacity_blocks,
        "deep_quota_factor": quota_factor,
        "bytes_per_query_xla_serve": out["serve"]["xla"],
        "bytes_per_query_pallas_serve": out["serve"]["pallas_block_scan"],
        "bytes_per_query_xla_deep": out["deep"]["xla"],
        "bytes_per_query_pallas_block_scan_deep":
            out["deep"]["pallas_block_scan"],
        "bytes_ratio_xla_over_pallas_serve": r_serve,
        "bytes_ratio_xla_over_pallas_deep": r_deep,
    }


def main(fast: bool = False, n_docs: Optional[int] = None,
         vocab_size: int = 65536, block_docs: int = 512,
         n_add: int = 2048, docs_per_commit: int = 256,
         serve_docs: Optional[int] = None, serve_queries: int = 200,
         wave: int = 64) -> dict:
    if n_docs is None:
        n_docs = 131_072 if fast else 1_000_000
    if serve_docs is None:
        serve_docs = 2048 if fast else 8192
    deep_docs = n_docs
    if fast:
        n_add, docs_per_commit = 512, 128

    print(f"== live index scale ({n_docs} docs, vocab {vocab_size}) ==")
    scale = bench_scale(n_docs, vocab_size, block_docs,
                        n_add, docs_per_commit)
    print(f"\n== live bytes-per-query ({serve_docs} vs {deep_docs} docs) ==")
    serving = bench_serving(serve_docs, deep_docs, serve_queries, wave)

    from benchmarks._results import record
    metrics = {**scale, **serving}
    record("index_bench",
           config={"fast": fast, "n_docs": n_docs,
                   "vocab_size": vocab_size, "block_docs": block_docs,
                   "serve_docs": serve_docs},
           metrics=metrics)
    return metrics


def _cli() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized: ~128k-doc scale stage")
    ap.add_argument("--n-docs", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=65536)
    ap.add_argument("--block-docs", type=int, default=512)
    ap.add_argument("--serve-docs", type=int, default=None)
    args = ap.parse_args()
    main(fast=args.fast, n_docs=args.n_docs, vocab_size=args.vocab,
         block_docs=args.block_docs, serve_docs=args.serve_docs)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    _cli()
