"""Kernel micro-benchmarks: wall-time of the jitted ops on this host
(CPU; interpret-mode Pallas) + derived bandwidth/throughput, plus the
analytic TPU-target roofline for each kernel (what the BlockSpec tiling
implies on v5e).  Prints ``name,us_per_call,derived`` CSV and records
``results/kernel_bench.json`` in the shared benchmarks/_results schema."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, iters: int = 20, warmup: int = 3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def rows():
    rng = np.random.default_rng(0)
    out = []

    # block_scan — the paper's hot loop (pure-jnp path is the wall-time
    # reference on CPU; kernel path validated in interpret mode)
    from repro.kernels.block_scan.ops import block_scan_reference
    nb, w = 64, 128
    occ = jnp.asarray(rng.integers(0, 2**32, (nb, 4, 4, w), dtype=np.uint32))
    allowed = jnp.ones((4, 4), bool)
    required = jnp.ones((4,), bool)
    present = jnp.ones((4,), bool)
    us = timeit(block_scan_reference, occ, allowed, required, present)
    bytes_scanned = occ.size * 4
    out.append(("block_scan_ref_64blk", us, f"{bytes_scanned/us/1e3:.2f}GB/s_host"))
    # v5e target: memory-bound at 819 GB/s -> per-1M-doc-query scan cost
    out.append(("block_scan_v5e_model", bytes_scanned / 819e9 * 1e6,
                "us_at_HBM_roofline"))

    # plane-pruned scan: a shallow 2-plane rule (e.g. mr_B — one present
    # term in U|T) streams only its active planes, so the v5e roofline
    # cost drops by T*F/n_active = 8x vs the full tile (the whole point
    # of the pallas_block_scan backend)
    shallow_active = 2
    bytes_pruned = nb * shallow_active * w * 4
    out.append(("block_scan_pruned_shallow_v5e_model",
                bytes_pruned / 819e9 * 1e6,
                f"us_at_HBM_roofline_{occ.size * 4 // bytes_pruned}x_fewer_bytes"))

    # flash attention vs naive reference (XLA path)
    from repro.kernels.flash_attention.ops import flash_attention_reference
    q = jnp.asarray(rng.normal(size=(1, 8, 512, 64)), jnp.float32)
    us = timeit(lambda a: flash_attention_reference(a, a, a, causal=True), q)
    flops = 4 * 8 * 512 * 512 * 64 / 2
    out.append(("attention_ref_512", us, f"{flops/us/1e3:.1f}GFLOPs_host"))

    # decode attention
    from repro.kernels.decode_attention.ops import decode_attention_reference
    qd = jnp.asarray(rng.normal(size=(4, 8, 64)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(4, 8, 4096, 64)), jnp.float32)
    us = timeit(lambda a, b: decode_attention_reference(a, b, b)[0], qd, kv)
    bytes_kv = kv.size * 4 * 2
    out.append(("decode_attn_ref_4k", us, f"{bytes_kv/us/1e3:.2f}GB/s_host"))
    out.append(("decode_attn_v5e_model", bytes_kv / 819e9 * 1e6, "us_at_HBM_roofline"))

    # embedding bag
    from repro.kernels.embedding_bag.ops import embedding_bag
    table = jnp.asarray(rng.normal(size=(100_000, 32)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 100_000, (1024, 8)).astype(np.int32))
    us = timeit(lambda t, i: embedding_bag(t, i, mode="sum"), table, idx)
    gathered = idx.size * 32 * 4
    out.append(("embedding_bag_1k x8", us, f"{gathered/us/1e3:.2f}GB/s_host"))

    # match-plan executor end-to-end (one rule over a 2048-doc index)
    return out


def main() -> None:
    from benchmarks._results import record

    print("name,us_per_call,derived")
    metrics = {}
    for name, us, derived in rows():
        print(f"{name},{us:.1f},{derived}")
        metrics[name] = {"us_per_call": us, "derived": derived}
    record("kernel_bench",
           config={"backend": jax.default_backend(),
                   "interpret_pallas": jax.default_backend() != "tpu"},
           metrics=metrics)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
