"""The served ranker, state bins and policies, made from the seed.

The benchmark makes the tables it serves with, so that its reference
takes nothing the program has made.  All of them come out of one jitted
call on the device, in the dtype the program serves them in (float32):

- ``l1``: the L1 ranker, an MLP of the configuration's widths
  (FEATURE_DIM -> hidden -> hidden -> 1).  Random normal weights scaled
  by fan-in, plus one hidden unit carried through every layer that
  reads a fixed relevance direction (title and body coverage, static
  rank), so that the served top-``keep`` lean towards judged documents
  and NCG@100 reads something other than noise.
- ``u_edges`` / ``v_edges``: the (u, v) state bins, ``sqrt(p)`` strata
  over u and ``p / sqrt(p)`` bins over v within each, at the
  configuration's ``p_bins``.  Edges are log-spaced over the ranges in
  the configuration's ``bins`` section, jittered from the seed.
- ``q``: one greedy Q-table per query category, ``(p, k_rules + 2)``,
  with match rules preferred over reset and stop by ``rule_bias``.
"""
from __future__ import annotations

import math

FEATURE_DIM = 15           # 3 x 4 fields + 3 (ranking/features.py layout)
# Feature columns of the relevance direction: title coverage, body
# coverage, static rank (fields in the order anchor, url, body, title).
RELEVANCE = {3: 0.6, 2: 0.4, 10: 0.25}


def make_weights(seed: int, cfg: dict) -> dict:
    """Numpy copies of every table (one jitted call on the default
    device, then one transfer to the host)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    hidden = int(cfg["widths"]["l1_hidden"])
    p = int(cfg["widths"]["p_bins"])
    n_actions = int(cfg["widths"]["k_rules"]) + 2
    k_rules = int(cfg["widths"]["k_rules"])
    pu = max(1, int(math.isqrt(p)))
    pv = max(1, p // pu)
    w = cfg["weights"]
    u_lo, u_hi = cfg["bins"]["u_range"]
    v_lo, v_hi = cfg["bins"]["v_range"]
    n_cat = int(cfg["n_categories"])

    def log_edges(key, n, lo, hi, lead=()):
        # n strictly increasing edges per row, log-spaced with jitter.
        base = jnp.linspace(math.log(lo), math.log(hi), n)
        step = (math.log(hi) - math.log(lo)) / max(n - 1, 1)
        jit = jax.random.uniform(key, (*lead, n), minval=-0.4, maxval=0.4)
        return jnp.exp(base + jit * step).astype(jnp.float32)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 6)
        rel = jnp.zeros((FEATURE_DIM,), jnp.float32)
        for col, wt in RELEVANCE.items():
            rel = rel.at[col].set(wt)
        w1 = jax.random.normal(ks[0], (FEATURE_DIM, hidden)) / math.sqrt(FEATURE_DIM)
        w2 = jax.random.normal(ks[1], (hidden, hidden)) / math.sqrt(hidden)
        w3 = jax.random.normal(ks[2], (hidden, 1)) / math.sqrt(hidden)
        g = float(w["relevance_gain"])
        w1 = w1.at[:, 0].set(rel * g)
        w2 = w2.at[0, :].set(0.0).at[:, 0].set(0.0).at[0, 0].set(g)
        w3 = w3.at[0, 0].set(g)
        l1 = {"w1": w1, "b1": jnp.zeros((hidden,)),
              "w2": w2, "b2": jnp.zeros((hidden,)),
              "w3": w3, "b3": jnp.full((1,), -float(w["score_offset"]))}
        l1 = {k: v.astype(jnp.float32) for k, v in l1.items()}
        u_edges = log_edges(ks[3], pu - 1, u_lo, u_hi)
        v_edges = log_edges(ks[4], pv - 1, v_lo, v_hi, lead=(pu,))
        q = jnp.abs(jax.random.normal(ks[5], (n_cat, p, n_actions))
                    * float(w["q_noise"]))
        q = q.at[:, :, :k_rules].add(float(w["rule_bias"])).astype(jnp.float32)
        return {"l1": l1, "u_edges": jnp.sort(u_edges),
                "v_edges": jnp.sort(v_edges, axis=-1), "q": q}

    out = jax.device_get(make(jax.random.key(seed % (2 ** 32))))
    return jax.tree_util.tree_map(np.asarray, out)
