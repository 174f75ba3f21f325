"""Synthetic web corpus with multi-field documents and static rank.

Documents carry four fields — Anchor (A), Url (U), Body (B), Title (T) —
mirroring the paper's example match rules.  Terms follow a Zipf
distribution; titles/urls/anchors are correlated subsets of the body so
that field-restricted match rules (e.g. ``term ∈ U|T``) behave the way
they do in a real web index: much sparser, but biased toward documents
for which the term is *topical*.

Documents are generated directly in static-rank order (doc id 0 = best
static rank).  High-rank documents receive more anchor text (popular
pages attract links), which is what makes shallow U|T|A scans effective
for navigational queries — the structural fact the paper's match plans
exploit.

Everything here is host-side numpy: this is the data-preparation layer
that feeds the JAX query-evaluation runtime.
"""
from __future__ import annotations

import contextlib
import dataclasses
import multiprocessing as mp
import os
from typing import List

import numpy as np

FIELDS = ("anchor", "url", "body", "title")
N_FIELDS = len(FIELDS)
A, U, B, T = range(N_FIELDS)

__all__ = ["FIELDS", "N_FIELDS", "A", "U", "B", "T", "CorpusConfig", "Corpus", "generate_corpus"]


@dataclasses.dataclass(frozen=True)
class CorpusConfig:
    n_docs: int = 8192
    vocab_size: int = 2048
    zipf_a: float = 1.15          # Zipf exponent for term frequencies
    body_terms: int = 48          # unique body terms per doc (mean)
    title_terms: int = 6
    url_terms: int = 3
    anchor_terms_base: int = 2    # anchors grow with static rank
    anchor_terms_top: int = 12
    n_topics: int = 64            # latent topics tying docs and queries together
    seed: int = 0


@dataclasses.dataclass
class Corpus:
    config: CorpusConfig
    # field_terms[f] : list of np.int32 arrays, one per doc (sorted unique term ids)
    field_terms: List[List[np.ndarray]]
    static_rank: np.ndarray       # (n_docs,) float32, descending in doc-id order
    doc_topic: np.ndarray         # (n_docs,) int32 latent topic per doc
    topic_terms: np.ndarray       # (n_topics, topic_vocab) int32 term ids per topic

    @property
    def n_docs(self) -> int:
        return self.config.n_docs


def _zipf_probs(vocab: int, a: float) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** (-a)
    return p / p.sum()


DOC_CHUNK = 1 << 16   # documents drawn from one random stream


def _generate_docs(config: CorpusConfig, rng: np.random.Generator,
                   topic_terms: np.ndarray, static_rank: np.ndarray,
                   doc_topic: np.ndarray) -> List[List[np.ndarray]]:
    """Per-field term arrays for a run of documents (one per entry of
    ``static_rank``/``doc_topic``), drawn from ``rng`` in doc order."""
    vocab = config.vocab_size
    topic_vocab = topic_terms.shape[1]
    # rng.choice(vocab, p=probs) without rebuilding the CDF per call:
    # the same cumsum, normalisation and uniform draws, so the same ids.
    cdf = _zipf_probs(vocab, config.zipf_a).cumsum()
    cdf /= cdf[-1]

    field_terms: List[List[np.ndarray]] = [[] for _ in range(N_FIELDS)]
    for d in range(len(static_rank)):
        topic = doc_topic[d]
        n_body = max(4, rng.poisson(config.body_terms))
        # Body = Zipf background + topical pocket.
        n_topical = max(2, n_body // 4)
        body = np.union1d(
            cdf.searchsorted(rng.random(n_body - n_topical), side="right"),
            rng.choice(topic_terms[topic], size=n_topical),
        ).astype(np.int32)

        # Title: topical subset of the body plus a couple of head terms.
        n_title = min(len(body), max(2, rng.poisson(config.title_terms)))
        topical_in_body = np.intersect1d(body, topic_terms[topic])
        title_pick = topical_in_body[: max(1, n_title // 2)]
        title = np.union1d(
            title_pick, rng.choice(body, size=max(1, n_title - len(title_pick)))
        ).astype(np.int32)

        # URL: small subset of title.
        n_url = min(len(title), max(1, rng.poisson(config.url_terms)))
        url = rng.choice(title, size=n_url, replace=False).astype(np.int32)
        url = np.unique(url)

        # Anchor: grows with static rank (popular pages get more links);
        # drawn from title+topic so navigational scans work.
        frac = static_rank[d]
        n_anchor = int(round(config.anchor_terms_base + frac * (config.anchor_terms_top - config.anchor_terms_base)))
        anchor_pool = np.union1d(title, topic_terms[topic][: topic_vocab // 2])
        n_anchor = min(len(anchor_pool), max(1, n_anchor))
        anchor = np.unique(rng.choice(anchor_pool, size=n_anchor, replace=False)).astype(np.int32)

        field_terms[A].append(anchor)
        field_terms[U].append(url)
        field_terms[B].append(np.unique(body))
        field_terms[T].append(np.unique(title))
    return field_terms


def _generate_chunk(args) -> List[tuple]:
    """Worker body: chunk ``i`` of the corpus from its own stream, each
    field packed as (flat terms, per-doc lengths) for a cheap return."""
    config, i, topic_terms, static_rank, doc_topic = args
    rng = np.random.default_rng([config.seed, i])
    fields = _generate_docs(config, rng, topic_terms, static_rank, doc_topic)
    return [(np.concatenate(lists), np.fromiter(map(len, lists), np.int64, len(lists)))
            for lists in fields]


def generate_corpus(config: CorpusConfig = CorpusConfig()) -> Corpus:
    """Documents come in chunks of :data:`DOC_CHUNK`.  The first is drawn
    from the corpus stream itself; chunk ``i`` >= 1 from a stream seeded
    by ``(seed, i)``, so later chunks are generated in parallel worker
    processes (numpy only, they never touch an accelerator) and the
    corpus is the same whatever the number of workers."""
    rng = np.random.default_rng(config.seed)
    vocab = config.vocab_size

    # Latent topics: each topic owns a pocket of moderately rare terms.
    topic_vocab = max(8, vocab // config.n_topics)
    topic_terms = np.zeros((config.n_topics, topic_vocab), dtype=np.int32)
    # Topic terms drawn from the rarer half of the vocabulary so topical
    # queries are CAT1-like (rare multi-term).
    rare_pool = np.arange(vocab // 4, vocab, dtype=np.int32)
    for k in range(config.n_topics):
        topic_terms[k] = rng.choice(rare_pool, size=topic_vocab, replace=False)

    # Static rank: exponential-ish decay, already sorted descending.
    static_rank = np.sort(rng.exponential(scale=1.0, size=config.n_docs))[::-1]
    static_rank = (static_rank / static_rank.max()).astype(np.float32)

    doc_topic = rng.integers(0, config.n_topics, size=config.n_docs).astype(np.int32)

    jobs = [(config, i, topic_terms, static_rank[lo:lo + DOC_CHUNK],
             doc_topic[lo:lo + DOC_CHUNK])
            for i, lo in enumerate(range(DOC_CHUNK, config.n_docs, DOC_CHUNK), 1)]
    n_workers = min(len(jobs), len(os.sched_getaffinity(0)))
    with (mp.get_context("spawn").Pool(n_workers) if jobs
          else contextlib.nullcontext()) as pool:
        # Workers start on the later chunks while this process draws the
        # first one.
        pending = pool.map_async(_generate_chunk, jobs) if jobs else None
        head = slice(0, DOC_CHUNK)
        field_terms = _generate_docs(config, rng, topic_terms,
                                     static_rank[head], doc_topic[head])
        for packed in (pending.get() if jobs else ()):
            for f, (flat, lens) in enumerate(packed):
                field_terms[f].extend(np.split(flat, np.cumsum(lens)[:-1]))

    return Corpus(
        config=config,
        field_terms=field_terms,
        static_rank=static_rank,
        doc_topic=doc_topic,
        topic_terms=topic_terms,
    )
