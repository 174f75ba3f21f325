"""Match rules: per-term conjunctions of per-field disjunctions.

A match rule (paper §3) is e.g.::

    mr_A -> (halloween ∈ A|U|B|T) ∧ (costumes ∈ A|U|B|T)
    mr_B -> (facebook  ∈ U|T)                       # 'login' relaxed

We represent a library of ``k`` rules as arrays so the whole engine is
JAX-traceable:

    allowed  (k, T, F) bool   fields a rule inspects per term slot
    required (k, T)    bool   whether the term participates in the conjunction
    du_quota (k,)      int32  stopping condition: max Δu per execution
    dv_quota (k,)      int32  stopping condition: max Δv per execution

``scan_block`` is the pure-jnp evaluation of one rule over one bitpacked
block — the math that the ``block_scan`` Pallas kernel tiles over many
blocks (kernels/block_scan/ref.py delegates here).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.index.corpus import A, U, B, T, N_FIELDS
from repro.index.builder import MAX_QUERY_TERMS

__all__ = ["RuleSet", "default_rule_library", "scan_block", "block_cost"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RuleSet:
    allowed: jnp.ndarray    # (k, T, F) bool
    required: jnp.ndarray   # (k, T) bool
    du_quota: jnp.ndarray   # (k,) int32
    dv_quota: jnp.ndarray   # (k,) int32

    @property
    def k(self) -> int:
        return self.allowed.shape[0]

    def tree_flatten(self):
        return (self.allowed, self.required, self.du_quota, self.dv_quota), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def gather(self, a: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
        """Rule parameters for action index ``a`` (traced)."""
        return (
            jnp.take(self.allowed, a, axis=0),
            jnp.take(self.required, a, axis=0),
            jnp.take(self.du_quota, a, axis=0),
            jnp.take(self.dv_quota, a, axis=0),
        )


def default_rule_library(
    du_scale: int = 1,
    dv_scale: int = 1,
    t: int = MAX_QUERY_TERMS,
) -> RuleSet:
    """Six hand-designed rules, strict → relaxed, mirroring the paper's
    examples.  Quotas are expressed in plane-blocks (Δu) and term matches
    (Δv); ``*_scale`` lets configs adapt them to corpus size.
    """
    F = N_FIELDS
    k = 6
    allowed = np.zeros((k, t, F), dtype=bool)
    required = np.zeros((k, t), dtype=bool)

    # mr0: every term in any of A|U|B|T  (the expensive, deep rule)
    allowed[0, :, :] = True
    required[0, :] = True
    # mr1: every term in U|T (navigational shallow scan)
    allowed[1, :, [U]] = True
    allowed[1, :, [T]] = True
    required[1, :] = True
    # mr2: every term in A|T (popularity-biased shallow scan)
    allowed[2, :, [A]] = True
    allowed[2, :, [T]] = True
    required[2, :] = True
    # mr3: every term in B|T (topical scan)
    allowed[3, :, [B]] = True
    allowed[3, :, [T]] = True
    required[3, :] = True
    # mr4: first two terms in any field, remaining terms relaxed
    allowed[4, :2, :] = True
    required[4, :2] = True
    # mr5: body-only conjunction (recall backstop)
    allowed[5, :, [B]] = True
    required[5, :] = True

    du = np.array([16, 4, 4, 8, 8, 12], dtype=np.int32) * du_scale
    dv = np.array([512, 64, 64, 256, 256, 384], dtype=np.int32) * dv_scale

    return RuleSet(
        allowed=jnp.asarray(allowed),
        required=jnp.asarray(required),
        du_quota=jnp.asarray(du),
        dv_quota=jnp.asarray(dv),
    )


def block_cost(allowed: jnp.ndarray, term_present: jnp.ndarray) -> jnp.ndarray:
    """Δu for scanning ONE block with a rule: number of (term, field)
    posting planes actually read.  (T, F) bool × (T,) bool → int32."""
    return jnp.sum(allowed & term_present[:, None], dtype=jnp.int32)


def scan_block(
    occ_block: jnp.ndarray,      # (T, F, W) uint32
    allowed: jnp.ndarray,        # (T, F) bool
    required: jnp.ndarray,       # (T,) bool
    term_present: jnp.ndarray,   # (T,) bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Evaluate one match rule over one block.

    Returns:
      match_words: (W,) uint32 — bit set iff the doc satisfies the rule
      v_inc:       ()  int32  — term-match count among inspected docs
                    (Σ_t popcount(∨_{f allowed} occ[t,f])), the paper's v.
    """
    mask = (allowed & term_present[:, None]).astype(jnp.uint32)          # (T, F)
    planes = occ_block * mask[..., None]                                 # (T, F, W)
    tf_or = lax.reduce_or(planes, (1,))                                  # (T, W)

    req = (required & term_present).astype(jnp.uint32)[:, None]          # (T, 1)
    # Non-required slots contribute all-ones to the conjunction.
    conj_in = tf_or | (jnp.uint32(0xFFFFFFFF) * (1 - req))
    match = lax.reduce_and(conj_in, (0,))                                # (W,)
    any_req = jnp.any(required & term_present)
    match = jnp.where(any_req, match, jnp.uint32(0))

    v_inc = jnp.sum(jax.lax.population_count(tf_or), dtype=jnp.int32)
    return match, v_inc
