"""Index substrate: bitpacking, corpus shape, inverted index, occupancy."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.index.blocks import pack_bits, unpack_bits, words_per_block
from repro.index.builder import MAX_QUERY_TERMS, build_index, query_occupancy
from repro.index.corpus import A, B, CorpusConfig, N_FIELDS, T, U, generate_corpus


@settings(deadline=None, max_examples=25)
@given(st.integers(1, 8), st.integers(0, 2**32 - 1))
def test_pack_unpack_roundtrip(words, seed):
    rng = np.random.default_rng(seed)
    bits = rng.random(words * 32) < 0.3
    assert (unpack_bits(pack_bits(bits)) == bits).all()


def test_pack_bit_order():
    bits = np.zeros(64, bool)
    bits[0] = bits[33] = True
    w = pack_bits(bits)
    assert w[0] == 1 and w[1] == 2


@pytest.fixture(scope="module")
def small():
    corpus = generate_corpus(CorpusConfig(n_docs=512, vocab_size=256, seed=3))
    index = build_index(corpus, block_docs=128)
    return corpus, index


def test_corpus_field_structure(small):
    corpus, _ = small
    # URL ⊆ Title by construction; anchors grow with static rank.
    for d in range(0, 512, 37):
        assert np.isin(corpus.field_terms[U][d], corpus.field_terms[T][d]).all()
    top_anchor = np.mean([len(corpus.field_terms[A][d]) for d in range(32)])
    tail_anchor = np.mean([len(corpus.field_terms[A][d]) for d in range(480, 512)])
    assert top_anchor > tail_anchor


def test_corpus_chunks_from_workers_match_their_streams(monkeypatch):
    """Chunks past the first come from worker processes; each must be
    exactly what its own (seed, chunk) stream draws in this process,
    the first must continue the corpus stream, and the field
    structure must hold across chunk seams."""
    from repro.index import corpus as corpus_mod

    monkeypatch.setattr(corpus_mod, "DOC_CHUNK", 64)
    cfg = CorpusConfig(n_docs=200, vocab_size=256, seed=5)
    corpus = generate_corpus(cfg)
    assert all(len(corpus.field_terms[f]) == 200 for f in range(N_FIELDS))
    for i, lo in enumerate(range(0, 200, 64)):
        sl = slice(lo, lo + 64)
        if i == 0:      # the corpus stream, after its global draws
            rng = np.random.default_rng(cfg.seed)
            for _ in range(cfg.n_topics):
                rng.choice(np.arange(cfg.vocab_size // 4, cfg.vocab_size),
                           size=corpus.topic_terms.shape[1], replace=False)
            rng.exponential(size=cfg.n_docs)
            rng.integers(0, cfg.n_topics, size=cfg.n_docs)
        else:
            rng = np.random.default_rng([cfg.seed, i])
        ref = corpus_mod._generate_docs(cfg, rng, corpus.topic_terms,
                                        corpus.static_rank[sl],
                                        corpus.doc_topic[sl])
        for f in range(N_FIELDS):
            got = corpus.field_terms[f][sl]
            assert len(got) == len(ref[f])
            assert all(np.array_equal(a, b) and a.dtype == b.dtype
                       for a, b in zip(got, ref[f]))
    for d in range(200):
        assert np.isin(corpus.field_terms[U][d], corpus.field_terms[T][d]).all()


def test_static_rank_sorted(small):
    corpus, _ = small
    assert (np.diff(corpus.static_rank) <= 0).all()
    assert corpus.static_rank.max() <= 1.0


def test_postings_sorted_and_df(small):
    corpus, index = small
    for f in range(N_FIELDS):
        for term in (1, 10, 100):
            ids = index.postings(term, f)
            assert (np.diff(ids) > 0).all()  # static-rank (doc id) order
            assert len(ids) == index.df[term, f]


def test_occupancy_matches_postings(small):
    corpus, index = small
    terms = [5, 17, 200]
    occ = query_occupancy(index, terms)
    assert occ.shape == (index.n_blocks, MAX_QUERY_TERMS, N_FIELDS, words_per_block(128))
    bits = unpack_bits(occ.transpose(1, 2, 0, 3).reshape(MAX_QUERY_TERMS, N_FIELDS, -1))
    for t, term in enumerate(terms):
        for f in range(N_FIELDS):
            member = np.zeros(index.padded_docs, bool)
            member[index.postings(term, f)] = True
            assert (bits[t, f] == member).all()
    # padded term slots are empty
    assert not bits[len(terms):].any()


# --------------------------------------------------- vectorized builder
def _reference_build_index(corpus, block_docs):
    """The pre-vectorization per-doc loop, kept verbatim as the oracle
    for the counting-sort builder."""
    from repro.index.builder import InvertedIndex

    vocab = corpus.config.vocab_size
    n_docs = corpus.n_docs
    indptrs, doc_id_arrays = [], []
    df = np.zeros((vocab, N_FIELDS), dtype=np.int32)
    doc_len = np.zeros((n_docs, N_FIELDS), dtype=np.int32)
    for f in range(N_FIELDS):
        counts = np.zeros(vocab, dtype=np.int64)
        for d in range(n_docs):
            terms = corpus.field_terms[f][d]
            counts[terms] += 1
            doc_len[d, f] = len(terms)
        df[:, f] = counts
        indptr = np.zeros(vocab + 1, dtype=np.int64)
        np.cumsum(counts, out=indptr[1:])
        ids = np.zeros(indptr[-1], dtype=np.int32)
        cursor = indptr[:-1].copy()
        for d in range(n_docs):
            terms = corpus.field_terms[f][d]
            ids[cursor[terms]] = d
            cursor[terms] += 1
        indptrs.append(indptr)
        doc_id_arrays.append(ids)
    return InvertedIndex(
        n_docs=n_docs, vocab_size=vocab, block_docs=block_docs,
        indptr=indptrs, doc_ids=doc_id_arrays,
        static_rank=corpus.static_rank, doc_len=doc_len, df=df)


def test_build_index_matches_reference_loop(small):
    corpus, index = small
    ref = _reference_build_index(corpus, block_docs=128)
    assert index.n_docs == ref.n_docs
    np.testing.assert_array_equal(index.df, ref.df)
    np.testing.assert_array_equal(index.doc_len, ref.doc_len)
    for f in range(N_FIELDS):
        np.testing.assert_array_equal(index.indptr[f], ref.indptr[f])
        np.testing.assert_array_equal(index.doc_ids[f], ref.doc_ids[f])


def test_build_index_from_pairs_dedup():
    from repro.index.builder import build_index_from_pairs

    rng = np.random.default_rng(21)
    n_docs, vocab = 64, 32
    docs = rng.integers(0, n_docs, size=300)
    terms = rng.integers(0, vocab, size=300)
    # duplicating every pair must not change the canonical postings
    soup = build_index_from_pairs(
        [np.concatenate([docs, docs])] * N_FIELDS,
        [np.concatenate([terms, terms])] * N_FIELDS,
        n_docs=n_docs, vocab_size=vocab,
        static_rank=np.linspace(1, 0, n_docs, dtype=np.float32),
        block_docs=32, dedup=True)
    clean = build_index_from_pairs(
        [docs] * N_FIELDS, [terms] * N_FIELDS,
        n_docs=n_docs, vocab_size=vocab,
        static_rank=np.linspace(1, 0, n_docs, dtype=np.float32),
        block_docs=32, dedup=True)
    for f in range(N_FIELDS):
        np.testing.assert_array_equal(soup.indptr[f], clean.indptr[f])
        np.testing.assert_array_equal(soup.doc_ids[f], clean.doc_ids[f])
    np.testing.assert_array_equal(soup.df, clean.df)


# --------------------------------------------------- blocks.py edge cases
def test_pack_bits_rejects_non_word_multiple():
    with pytest.raises(ValueError, match="multiple of 32"):
        pack_bits(np.zeros(33, bool))


def test_words_per_block_rejects_non_word_multiple():
    with pytest.raises(ValueError, match="multiple of 32"):
        words_per_block(100)
    assert words_per_block(128) == 4


def test_pack_bits_empty_plane_is_zero_words():
    w = pack_bits(np.zeros((3, 64), bool))
    assert w.shape == (3, 2) and not w.any()
    assert pack_bits(np.ones(32, bool))[0] == np.uint32(0xFFFFFFFF)


def test_occupancy_tail_block_zero_padded():
    """n_docs not a multiple of block_docs: the tail block's padding
    bits (docs beyond n_docs) must be zero in every plane."""
    from repro.index.builder import build_index_from_pairs

    n_docs, vocab, block_docs = 100, 16, 64     # padded to 128
    docs = np.arange(n_docs, dtype=np.int64)
    terms = (docs % vocab).astype(np.int64)     # every doc posts
    index = build_index_from_pairs(
        [docs] * N_FIELDS, [terms] * N_FIELDS,
        n_docs=n_docs, vocab_size=vocab,
        static_rank=np.linspace(1, 0, n_docs, dtype=np.float32),
        block_docs=block_docs, dedup=False)
    occ = query_occupancy(index, list(range(MAX_QUERY_TERMS)))
    bits = unpack_bits(
        occ.transpose(1, 2, 0, 3).reshape(MAX_QUERY_TERMS, N_FIELDS, -1))
    assert bits.shape[-1] == index.padded_docs == 128
    assert bits[..., :n_docs].any()             # real docs present
    assert not bits[..., n_docs:].any()         # padding strictly zero


def test_doc_bit_matches_unpack():
    from repro.index.blocks import doc_bit

    rng = np.random.default_rng(22)
    bits = rng.random(128) < 0.4
    words = pack_bits(bits)
    for d in (0, 31, 32, 77, 127):
        assert bool(doc_bit(words, np.int32(d))) == bits[d]
