"""Per-kernel correctness: Pallas (interpret mode on CPU) vs pure-jnp
oracles, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.block_scan.ops import block_scan, block_scan_reference
from repro.kernels.decode_attention.ops import (
    decode_attention,
    decode_attention_reference,
    merge_partials,
)
from repro.kernels.embedding_bag.ops import embedding_bag, embedding_bag_kernel
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import flash_attention, flash_attention_reference


# -------------------------------------------------------------- block_scan
@pytest.mark.parametrize("nb,w,bb", [(4, 16, 2), (16, 128, 8), (5, 32, 4), (1, 8, 8)])
def test_block_scan_shapes(nb, w, bb):
    rng = np.random.default_rng(nb * 100 + w)
    occ = jnp.asarray(rng.integers(0, 2**32, size=(nb, 4, 4, w), dtype=np.uint32))
    allowed = jnp.asarray(rng.random((4, 4)) < 0.5)
    required = jnp.asarray(rng.random(4) < 0.7)
    present = jnp.asarray(np.array([1, 1, 1, 0], bool))
    m1, v1, c1 = block_scan(occ, allowed, required, present, block_bb=bb)
    m2, v2, c2 = block_scan_reference(occ, allowed, required, present)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(v1) == np.asarray(v2)).all()
    assert (np.asarray(c1) == np.asarray(c2)).all()


@settings(deadline=None, max_examples=15)
@given(st.integers(0, 2**31 - 1))
def test_block_scan_property(seed):
    rng = np.random.default_rng(seed)
    nb = int(rng.integers(1, 9))
    occ = jnp.asarray(rng.integers(0, 2**32, size=(nb, 4, 4, 8), dtype=np.uint32))
    allowed = jnp.asarray(rng.random((4, 4)) < 0.6)
    required = jnp.asarray(rng.random(4) < 0.6)
    present = jnp.asarray(rng.random(4) < 0.8)
    m1, v1, c1 = block_scan(occ, allowed, required, present, block_bb=4)
    m2, v2, c2 = block_scan_reference(occ, allowed, required, present)
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(v1) == np.asarray(v2)).all()


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal,dtype",
    [
        (1, 4, 4, 128, 128, 64, True, jnp.float32),
        (2, 8, 2, 256, 256, 64, True, jnp.float32),    # GQA 4:1
        (1, 6, 2, 128, 128, 128, True, jnp.bfloat16),  # GQA 3:1, bf16
        (1, 2, 2, 128, 384, 64, False, jnp.float32),   # cross/bidir, Skv > Sq
        (1, 4, 1, 100, 200, 64, True, jnp.float32),    # ragged -> padding path
    ],
)
def test_flash_attention_vs_ref(b, hq, hkv, sq, skv, d, causal, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, skv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = flash_attention_reference(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_block_size_invariance():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    o1 = flash_attention(q, k, v, block_q=64, block_k=64)
    o2 = flash_attention(q, k, v, block_q=128, block_k=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5, rtol=1e-5)


# --------------------------------------------------------- decode attention
@pytest.mark.parametrize(
    "b,hq,hkv,s,d,dtype",
    [
        (2, 8, 8, 512, 64, jnp.float32),       # MHA
        (2, 8, 2, 1024, 64, jnp.float32),      # GQA 4:1
        (1, 48, 8, 640, 128, jnp.bfloat16),    # grok-like 6:1, ragged S
        (1, 16, 16, 300, 64, jnp.float32),     # MLA-ish wide, pad path
    ],
)
def test_decode_attention_vs_ref(b, hq, hkv, s, d, dtype):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    out, m, l = decode_attention(q, k, v, block_k=256)
    ref, mr, lr = decode_attention_reference(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_decode_partial_merge_equals_full():
    """Sequence-sharded decode: LSE-merged shard partials == full attention.
    This is the long_500k KV-sequence-sharding correctness argument."""
    rng = np.random.default_rng(3)
    b, h, s, d, shards = 2, 4, 512, 64, 4
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    accs, ms, ls = [], [], []
    for i in range(shards):
        sl = slice(i * s // shards, (i + 1) * s // shards)
        a, m, l = decode_attention(q, k[:, :, sl], v[:, :, sl], block_k=64, return_partial=True)
        accs.append(a.astype(jnp.float32)); ms.append(m); ls.append(l)
    merged = merge_partials(accs, ms, ls)
    full, _, _ = decode_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full), atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ embedding bag
@pytest.mark.parametrize(
    "v,e,b,l,mode,dtype",
    [
        (64, 8, 4, 6, "sum", jnp.float32),
        (128, 16, 8, 3, "mean", jnp.float32),
        (1000, 32, 16, 10, "sum", jnp.float32),
        (64, 128, 4, 4, "mean", jnp.bfloat16),
    ],
)
def test_embedding_bag_kernel_vs_ref(v, e, b, l, mode, dtype):
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.normal(size=(v, e)), dtype)
    idx = rng.integers(-1, v, size=(b, l)).astype(np.int32)  # includes padding
    out = embedding_bag_kernel(table, jnp.asarray(idx), mode=mode)
    ref = embedding_bag_ref(table, jnp.asarray(idx), mode=mode)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_embedding_bag_weighted():
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 32, size=(4, 5)).astype(np.int32))
    w = jnp.asarray(rng.normal(size=(4, 5)).astype(np.float32))
    out = embedding_bag_kernel(table, idx, w, mode="sum")
    ref = embedding_bag_ref(table, idx, w, mode="sum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 2**31 - 1))
def test_embedding_bag_property(seed):
    """Permuting items within a bag leaves the sum unchanged."""
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.normal(size=(50, 16)).astype(np.float32))
    idx = rng.integers(0, 50, size=(3, 8)).astype(np.int32)
    perm = np.stack([r[rng.permutation(8)] for r in idx])
    o1 = embedding_bag(table, jnp.asarray(idx), mode="sum")
    o2 = embedding_bag(table, jnp.asarray(perm), mode="sum")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5, rtol=1e-5)


# ------------------------------------------------- plane-pruned block_scan
@pytest.mark.parametrize("n_terms,fields", [(2, (1, 3)), (3, (0, 1, 2, 3)), (4, (2,))])
def test_block_scan_pruned_vs_ref(n_terms, fields):
    """§Perf hillclimb #3: the pruned kernel streams only active planes
    and must match the full-scan oracle bit-exactly."""
    from repro.kernels.block_scan.block_scan_pruned import block_scan_pruned_pallas

    rng = np.random.default_rng(n_terms * 10 + len(fields))
    occ = jnp.asarray(rng.integers(0, 2**32, (8, 4, 4, 16), dtype=np.uint32))
    allowed = np.zeros((4, 4), bool)
    for f in fields:
        allowed[:, f] = True
    required = np.zeros(4, bool); required[:n_terms] = True
    present = np.zeros(4, bool); present[:n_terms] = True
    m1, v1, c1 = block_scan_pruned_pallas(occ, allowed, required, present)
    m2, v2, c2 = block_scan_reference(
        occ, jnp.asarray(allowed), jnp.asarray(required), jnp.asarray(present))
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(v1) == np.asarray(v2)).all()
    assert (np.asarray(c1) == np.asarray(c2)).all()


@pytest.mark.parametrize(
    "allowed_rows,required,present",
    [
        # zero ACTIVE planes: the padding grid step must not leak plane
        # (0, 0)'s occupancy into tf (v_inc = 0, match = 0)
        ((), (True, False, False, False), (True, True, True, True)),
        # allowed planes but term_present all false — also zero active
        ((0, 1, 2, 3), (True, True, True, True), (False,) * 4),
        # zero REQUIRED terms: match empties (any_req) but v_inc still
        # counts term hits among the planes the rule paid u to inspect
        ((0, 1), (False, False, False, False), (True, True, True, True)),
    ],
)
def test_block_scan_pruned_degenerate_rules_match_reference(
        allowed_rows, required, present):
    """Degenerate-rule semantics are pinned against block_scan_reference
    (intended: v follows u — inspected planes count term hits whether or
    not the conjunction can match; zero inspected planes count nothing)."""
    from repro.kernels.block_scan.block_scan_pruned import block_scan_pruned_pallas

    rng = np.random.default_rng(3)
    occ = jnp.asarray(rng.integers(0, 2**32, (6, 4, 4, 16), dtype=np.uint32))
    allowed = np.zeros((4, 4), bool)
    for t in allowed_rows:
        allowed[t, :] = True
    required = np.asarray(required)
    present = np.asarray(present)
    m1, v1, c1 = block_scan_pruned_pallas(occ, allowed, required, present)
    m2, v2, c2 = block_scan_reference(
        occ, jnp.asarray(allowed), jnp.asarray(required), jnp.asarray(present))
    assert (np.asarray(m1) == np.asarray(m2)).all()
    assert (np.asarray(v1) == np.asarray(v2)).all()
    assert (np.asarray(c1) == np.asarray(c2)).all()


def test_block_scan_pruned_chunk_vs_ref():
    """The chunked traced-rule kernel (serving backend path) against the
    full-scan oracle: per-lane rules, block-start offsets, end-of-index
    clamping."""
    from repro.kernels.block_scan.block_scan_pruned import (
        block_scan_pruned_chunk, build_rule_meta,
    )

    rng = np.random.default_rng(9)
    b, nb, t, f, w, chunk = 3, 8, 4, 4, 16, 4
    occ = jnp.asarray(rng.integers(0, 2**32, (b, nb, t, f, w), dtype=np.uint32))
    allowed = jnp.asarray(rng.random((b, t, f)) < 0.5)
    required = jnp.asarray(rng.random((b, t)) < 0.6)
    present = jnp.asarray(rng.random((b, t)) < 0.8)
    allowed = allowed.at[2].set(False)            # zero-active lane
    bp = jnp.asarray([0, 3, 6], jnp.int32)        # lane 2 runs off the end

    meta = build_rule_meta(allowed, required, present, bp)
    m, v, c = block_scan_pruned_chunk(
        occ.reshape(b, nb, t * f, w), meta, chunk=chunk, n_terms=t)
    for lane in range(b):
        for j in range(chunk):
            blk = min(int(bp[lane]) + j, nb - 1)
            mr, vr, cr = block_scan_reference(
                occ[lane, blk][None], allowed[lane], required[lane],
                present[lane])
            assert (np.asarray(m[lane, j]) == np.asarray(mr[0])).all()
            assert int(v[lane, j]) == int(vr[0])
            assert int(c[lane, j]) == int(cr[0])


def test_block_scan_pruned_chunk_reads_only_active_planes():
    """Bytes streamed follow the rule's active planes: the meta lists
    exactly the active (term, field) planes first, one DMA each, and
    whatever sits in the inactive planes never reaches the result."""
    from repro.core.match_rules import block_cost
    from repro.kernels.block_scan.block_scan_pruned import (
        META_BP_COL, block_scan_pruned_chunk, build_rule_meta,
    )

    rng = np.random.default_rng(11)
    b, nb, t, f, w, chunk = 4, 6, 4, 4, 16, 3
    allowed = jnp.asarray(rng.random((b, t, f)) < 0.4)
    allowed = allowed.at[3].set(False)               # zero-active lane
    required = jnp.asarray(rng.random((b, t)) < 0.6)
    present = jnp.asarray(rng.random((b, t)) < 0.9)
    meta = build_rule_meta(allowed, required, present,
                           jnp.asarray([0, 2, 3, 5], jnp.int32))
    act = np.asarray(allowed & present[:, :, None]).reshape(b, t * f)
    for lane in range(b):
        n_active = int(meta[lane, 1, META_BP_COL])
        assert n_active == int(block_cost(allowed[lane], present[lane]))
        assert sorted(np.asarray(meta[lane, 0, :n_active]).tolist()) == \
            np.flatnonzero(act[lane]).tolist()

    occ = rng.integers(0, 2**32, (b, nb, t * f, w), dtype=np.uint32)
    noise = rng.integers(0, 2**32, occ.shape, dtype=np.uint32)
    occ_noisy = np.where(act[:, None, :, None], occ, noise)
    outs = [block_scan_pruned_chunk(jnp.asarray(o), meta, chunk=chunk, n_terms=t)
            for o in (occ, occ_noisy)]
    for a, c in zip(*outs):
        assert (np.asarray(a) == np.asarray(c)).all()


def test_resolve_interpret_is_decided_at_call_time(monkeypatch):
    """Interpret mode follows the backend at CALL time; on a TPU it is
    never chosen implicitly, and asking for it there is an error."""
    from repro.kernels.common import resolve_interpret

    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    assert resolve_interpret(False) is False
    with pytest.raises(RuntimeError, match="interpret mode"):
        resolve_interpret(True)
