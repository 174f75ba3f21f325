"""The served log: the program's judged log followed by unjudged queries
drawn from the seed by the same law (``bench/sut.py::extend_log``), and
the pre-window check that the mix cannot run out of it."""
import jax
import numpy as np
import pytest

from bench import loadgen, spec, sut
from bench.tests import tiny

from repro.data.querylog import CAT2, QueryLogConfig, generate_querylog
from repro.index.builder import MAX_QUERY_TERMS, build_index
from repro.index.corpus import B, T, U, CorpusConfig, generate_corpus

SEED = 2**31 + 23
N_JUDGED_LOG = 64
N_SERVED = 2048
MIN_UNIQUE = 1400             # tiny: 128 best-ranked docs feed every CAT2 query


def served_config() -> dict:
    cfg = tiny.tiny_config()
    cfg["querylog"]["n_queries"] = N_JUDGED_LOG
    cfg["served_log"] = {"n_queries": N_SERVED, "min_unique": MIN_UNIQUE}
    return cfg


@pytest.fixture(scope="module")
def world():
    cfg = served_config()
    n_docs = int(cfg["n_blocks"]) * int(cfg["widths"]["block_docs"])
    corpus = generate_corpus(CorpusConfig(n_docs=n_docs, seed=SEED,
                                          **cfg["corpus"]))
    index = build_index(corpus, block_docs=int(cfg["widths"]["block_docs"]))
    log = generate_querylog(corpus, index,
                            QueryLogConfig(seed=SEED, **cfg["querylog"]))
    return cfg, corpus, log, sut.extend_log(log, corpus, N_SERVED, cfg, SEED)


def law_violations(log, corpus, rows) -> list:
    """Rows of ``log`` whose terms break ``generate_querylog``'s law:
    distinct terms, a CAT2 query 2-3 of its seed document's title and url
    terms, that document among the n_docs // 16 (at least 64) best, a
    CAT1 query 3 to MAX_QUERY_TERMS of its seed document's topical body
    terms (all its body terms where fewer than 2 are topical), fewer
    only where that pool is smaller."""
    bad = []
    for q in rows:
        d = int(log.seed_doc[q])
        got = log.terms[q, :log.n_terms[q]]
        assert (log.terms[q, log.n_terms[q]:] == -1).all()
        if log.category[q] == CAT2:
            pool = set(corpus.field_terms[T][d]) | set(corpus.field_terms[U][d])
            lo, hi = 2, 3
            in_range = d < max(64, corpus.n_docs // 16)
        else:
            body = set(corpus.field_terms[B][d])
            pool = body & set(corpus.topic_terms[corpus.doc_topic[d]])
            pool = pool if len(pool) >= 2 else body
            lo, hi = 3, MAX_QUERY_TERMS
            in_range = True
        n_ok = min(lo, len(pool)) <= len(got) <= hi
        if not (in_range and n_ok and len(set(got)) == len(got)
                and set(got.tolist()) <= pool):
            bad.append(int(q))
    return bad


def test_judged_prefix_is_the_programs_log(world):
    cfg, corpus, log, ext = world
    n = log.n_queries
    assert ext.n_queries == N_SERVED
    for field in ("terms", "n_terms", "category", "judged_ids",
                  "judged_gains", "seed_doc"):
        np.testing.assert_array_equal(getattr(ext, field)[:n],
                                      getattr(log, field), err_msg=field)
        assert getattr(ext, field).dtype == getattr(log, field).dtype, field


def test_extension_is_unjudged_and_fixed_by_the_seed(world):
    cfg, corpus, log, ext = world
    n = log.n_queries
    assert (ext.judged_ids[n:] == -1).all() and (ext.judged_gains[n:] == 0).all()
    again = sut.extend_log(log, corpus, N_SERVED, cfg, SEED)
    other = sut.extend_log(log, corpus, N_SERVED, cfg, SEED + 1)
    np.testing.assert_array_equal(again.terms, ext.terms)
    np.testing.assert_array_equal(again.category, ext.category)
    assert not np.array_equal(other.terms[n:], ext.terms[n:])


def test_extension_category_share_is_frac_cat2(world):
    cfg, corpus, log, ext = world
    cats = ext.category[log.n_queries:]
    p = float(cfg["querylog"]["frac_cat2"])
    tol = 4.0 * np.sqrt(p * (1.0 - p) / len(cats))
    assert abs(float(np.mean(cats == CAT2)) - p) <= tol


def test_extension_terms_follow_the_programs_law(world):
    cfg, corpus, log, ext = world
    # The predicate holds the program's own queries too, so it states
    # the law the program samples by.
    assert law_violations(log, corpus, range(log.n_queries)) == []
    assert law_violations(ext, corpus, range(log.n_queries, N_SERVED)) == []


def test_popularity_is_zipf_over_the_whole_log(world):
    cfg, corpus, log, ext = world
    a = float(cfg["querylog"]["zipf_a"])
    want = (1.0 + np.arange(N_SERVED)) ** -a
    np.testing.assert_allclose(np.sort(ext.popularity)[::-1],
                               want / want.sum(), rtol=1e-12)
    assert ext.popularity[ext.category == CAT2].min() > \
        ext.popularity[ext.category != CAT2].max()


def test_unique_plan_holds_the_stated_minimum(world):
    cfg, corpus, log, ext = world
    for seed in (SEED, 7, 2**31 + 101):
        seq = loadgen.plan_queries(ext.terms, ext.category, ext.popularity,
                                   {"select": "unique"}, seed)
        assert len(seq) >= cfg["served_log"]["min_unique"], seed


def test_short_extension_is_refused(world):
    cfg, corpus, log, _ = world
    with pytest.raises(ValueError, match="below the judged log"):
        sut.extend_log(log, corpus, log.n_queries - 1, cfg, SEED)


def _run(monkeypatch, tmp_path, cfg, seed=2**31 + 41):
    root = tiny.make_root(tmp_path, tiny.CLOSED, cfg=cfg)
    with tiny.cpu_harness(monkeypatch, root) as br:
        return br.run_cell(spec.load_cell("tiny.t", root), seed, 1.5, False,
                           tiny.STAMP, jax.devices()[:1], out=lambda s: None,
                           err=lambda s: None)


def test_run_serving_extension_queries_is_correct(monkeypatch, tmp_path):
    import bench.run as br

    served = []
    measure = br.Harness.measure

    def spy(self, *a, **k):
        window, *rest = measure(self, *a, **k)
        served.extend(r.qid for r in window.completed)
        return (window, *rest)

    monkeypatch.setattr(br.Harness, "measure", spy)
    res = _run(monkeypatch, tmp_path, served_config())
    assert res["correct"] is True and res["failed"] == 0
    assert sum(q >= N_JUDGED_LOG for q in served) > len(served) // 2


def test_altered_id_on_extension_queries_is_incorrect(monkeypatch, tmp_path):
    from repro.serving.executor import ShardedExecutor

    from bench.tests.test_bench_faults import answer_altered

    execute = ShardedExecutor.execute
    monkeypatch.setattr(
        ShardedExecutor, "execute",
        lambda self, *a, **k: answer_altered(*execute(self, *a, **k)))
    res = _run(monkeypatch, tmp_path, served_config())
    assert res["correct"] is False
    assert res["check"]["id_mismatch"]["value"] > 0


def test_supply_under_the_minimum_stops_before_the_window(monkeypatch,
                                                          tmp_path):
    cfg = served_config()
    cfg["served_log"]["min_unique"] = N_SERVED + 1

    def drive(*a, **k):
        raise AssertionError("the window started")

    monkeypatch.setattr(loadgen, "drive", drive)
    with pytest.raises(ValueError, match="min_unique"):
        _run(monkeypatch, tmp_path, cfg)
