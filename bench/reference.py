"""Plain reference of the served match-planning path, in numpy.

It imports nothing of the program.  It reads the seed's data (each
document's term ids per field and its static rank, each logged query's
terms and category) and the tables the benchmark made
(``bench/weights.py``), and recomputes every layer of the served path
one query at a time:

1. postings and document frequencies, from the documents' term lists;
2. the query's occupancy, as dense per-(term, field) document bitmaps;
3. the greedy policy rollout over ``t_max`` steps: state bin of
   (u, v), argmax of the category's Q row, and each match rule scanned
   block by block until its Δu or Δv quota, the end of the shard or the
   u budget (paper §3);
4. the merge of the shards' candidates by static rank (ascending id);
5. the L1 features and MLP score of every candidate, in float64, and
   the prune to the ``keep`` best, ties to the lower position.

``l1_precision="high"`` computes step 5's matmuls in three bfloat16
passes instead: the control, which the comparison in ``bench/check.py``
must refuse.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import numpy as np

N_FIELDS = 4
BODY = 2                      # field order: anchor, url, body, title


@dataclasses.dataclass
class Answer:
    u: int
    cand: np.ndarray          # merged candidates, ascending doc id
    scores: np.ndarray        # L1 score of each candidate (float64)
    ids: np.ndarray           # (keep,) served ids, -1 pad
    top: np.ndarray           # (keep,) their scores, -inf pad


class Postings:
    """Per-field postings of the terms some set of queries uses, built
    from the documents' own term lists."""

    def __init__(self, field_terms: Sequence[Sequence[np.ndarray]],
                 static_rank: np.ndarray, terms_needed: np.ndarray):
        self.n_docs = len(static_rank)
        self.static_rank = np.asarray(static_rank, np.float64)
        need = np.unique(np.asarray(terms_needed, np.int64))
        self.doc_len = np.zeros((self.n_docs, N_FIELDS), np.float64)
        self.lists: List[Dict[int, np.ndarray]] = []
        for f in range(N_FIELDS):
            lens = np.fromiter(map(len, field_terms[f]), np.int64,
                               count=self.n_docs)
            self.doc_len[:, f] = lens
            flat = np.concatenate(field_terms[f]).astype(np.int64)
            docs = np.repeat(np.arange(self.n_docs, dtype=np.int64), lens)
            keep = np.isin(flat, need)
            t, d = flat[keep], docs[keep]
            order = np.argsort(t, kind="stable")
            t, d = t[order], d[order]
            bounds = np.searchsorted(t, need)
            ends = np.searchsorted(t, need, side="right")
            self.lists.append({int(term): d[lo:hi]
                               for term, lo, hi in zip(need, bounds, ends)})

    def df_body(self, term: int) -> int:
        return len(self.lists[BODY].get(int(term), ()))


def state_bin(u_edges: np.ndarray, v_edges: np.ndarray, u: int, v: int) -> int:
    uf, vf = np.float32(u), np.float32(v)
    s = int(np.searchsorted(u_edges, uf, side="right"))
    vb = int(np.sum(v_edges[s] <= vf))
    return s * (v_edges.shape[1] + 1) + vb


def _rollout(occ, tp, lo_doc, n_blocks, block_docs, rules, q_row_of,
             u_edges, v_edges, widths):
    """One shard's greedy rollout; returns (u, candidate ids)."""
    k = len(rules["du_quota"])
    cap = int(widths["max_candidates"])
    budget = int(widths["u_budget"])
    allowed = np.asarray(rules["allowed"], bool)       # (k, T, F)
    required = np.asarray(rules["required"], bool)     # (k, T)
    n_t = len(tp)
    u = v = bp = 0
    done = False
    matched = np.zeros(n_blocks * block_docs, bool)
    cand: List[np.ndarray] = []
    cnt = 0
    for _ in range(int(widths["t_max"])):
        a = int(np.argmax(q_row_of(state_bin(u_edges, v_edges, u, v))))
        if a < k and not done:
            mask = allowed[a] & tp[:, None]
            u_inc = int(mask.sum())
            req = required[a] & tp
            du, dv = int(rules["du_quota"][a]), int(rules["dv_quota"][a])
            u0, v0 = u, v
            while (u - u0 < du and v - v0 < dv and bp < n_blocks
                   and u < budget):
                lo = bp * block_docs
                sl = slice(lo_doc + lo, lo_doc + lo + block_docs)
                tf_or = [np.zeros(block_docs, bool) for _ in range(n_t)]
                for t in range(n_t):
                    for f in range(N_FIELDS):
                        if mask[t, f]:
                            tf_or[t] |= occ[t][f][sl]
                if req.any():
                    match = np.logical_and.reduce(
                        [tf_or[t] for t in range(n_t) if req[t]])
                else:
                    match = np.zeros(block_docs, bool)
                new = match & ~matched[lo:lo + block_docs]
                matched[lo:lo + block_docs] |= match
                new_ids = lo_doc + lo + np.flatnonzero(new)
                cand.append(new_ids[:max(0, cap - cnt)])
                cnt = min(cnt + len(new_ids), cap)
                u += u_inc
                v += int(sum(int(x.sum()) for x in tf_or))
                bp += 1
        if a == k and not done:
            bp = 0
        done = done or a == k + 1 or u >= budget
    ids = np.concatenate(cand) if cand else np.zeros(0, np.int64)
    return u, ids


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _dot_high(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """A float32 matmul at ``high`` precision: three bfloat16 passes
    (hi*hi + hi*lo + lo*hi, each operand split into its bfloat16 head
    and the bfloat16 of its remainder), accumulated in float32."""
    a, b = a.astype(np.float32), b.astype(np.float32)
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return (ah @ bh + ah @ bl + al @ bh).astype(np.float32)


def l1_scores(feats: np.ndarray, l1: Dict[str, np.ndarray],
              precision: str = "float64") -> np.ndarray:
    """The L1 MLP, relu - relu - sigmoid: in float64, or (the control)
    in float32 with every matmul at ``high`` precision, the step below
    the ``highest`` the configuration serves at."""
    if precision == "float64":
        p = {k: np.asarray(v, np.float64) for k, v in l1.items()}
        h = np.maximum(feats @ p["w1"] + p["b1"], 0.0)
        h = np.maximum(h @ p["w2"] + p["b2"], 0.0)
        z = (h @ p["w3"] + p["b3"])[:, 0]
        return 1.0 / (1.0 + np.exp(-z))
    if precision != "high":
        raise ValueError(f"unknown L1 precision {precision!r}")
    p = {k: np.asarray(v, np.float32) for k, v in l1.items()}
    h = np.maximum(_dot_high(feats, p["w1"]) + p["b1"], np.float32(0))
    h = np.maximum(_dot_high(h, p["w2"]) + p["b2"], np.float32(0))
    z = (_dot_high(h, p["w3"]) + p["b3"])[:, 0]
    return (1.0 / (1.0 + np.exp(-z))).astype(np.float64)


def features(occ, tp, idf, docs, postings: Postings) -> np.ndarray:
    """(len(docs), 15) L1 features: per-field term coverage, per-field
    IDF share, share of terms matched, all matched, static rank, and
    per-field log length (ranking features of the served path)."""
    n_t = len(tp)
    hits = np.zeros((len(docs), n_t, N_FIELDS), np.float64)
    for t in range(n_t):
        if tp[t]:
            for f in range(N_FIELDS):
                hits[:, t, f] = occ[t][f][docs]
    tpf = tp.astype(np.float64)
    nt = max(tpf.sum(), 1.0)
    field_cov = hits.sum(1) / nt
    idf_sum = max(float((idf * tpf).sum()), 1e-6)
    field_idf = (hits * idf[None, :, None]).sum(1) / idf_sum
    any_field = hits.max(2)
    terms_matched = any_field.sum(1) / nt
    all_matched = (any_field.sum(1) >= nt).astype(np.float64)
    doc_len = np.log1p(postings.doc_len[docs]) / math.log(256.0)
    return np.concatenate(
        [field_cov, field_idf, terms_matched[:, None], all_matched[:, None],
         postings.static_rank[docs][:, None], doc_len], axis=1)


def answer(postings: Postings, terms_row: np.ndarray, category: int,
           weights: dict, cfg: dict, l1_precision: str = "float64") -> Answer:
    """The reference's answer to one logged query."""
    widths = cfg["widths"]
    block_docs = int(widths["block_docs"])
    n_blocks = int(cfg["n_blocks"])
    n_shards = int(cfg["engine"]["n_shards"])
    keep = int(widths["keep"])
    n_pad = n_blocks * block_docs
    terms = [int(t) for t in terms_row if t >= 0]
    n_t = len(terms_row)
    tp = np.array([i < len(terms) for i in range(n_t)])
    occ = []
    for i in range(n_t):
        row = []
        for f in range(N_FIELDS):
            bits = np.zeros(n_pad, bool)
            if i < len(terms):
                bits[postings.lists[f].get(terms[i], np.zeros(0, np.int64))] = True
            row.append(bits)
        occ.append(row)
    n_docs = postings.n_docs
    idf = np.array([math.log(n_docs / (1.0 + postings.df_body(terms[i])))
                    if i < len(terms) else 0.0 for i in range(n_t)])
    q = np.asarray(weights["q"][category])
    per_shard = n_blocks // n_shards
    u_total, shard_ids = 0, []
    for s in range(n_shards):
        u, ids = _rollout(occ, tp, s * per_shard * block_docs, per_shard,
                          block_docs, cfg["rules"], lambda b: q[b],
                          np.asarray(weights["u_edges"]),
                          np.asarray(weights["v_edges"]), widths)
        u_total += u
        shard_ids.append(ids)
    cand = np.sort(np.concatenate(shard_ids))[:int(widths["max_candidates"])]
    scores = l1_scores(features(occ, tp, idf, cand, postings),
                       weights["l1"], l1_precision) if len(cand) else np.zeros(0)
    order = sorted(range(len(cand)), key=lambda i: (-scores[i], i))[:keep]
    ids = np.full(keep, -1, np.int64)
    top = np.full(keep, -np.inf)
    ids[:len(order)] = cand[order]
    top[:len(order)] = scores[order]
    return Answer(u=u_total, cand=cand, scores=scores, ids=ids, top=top)
