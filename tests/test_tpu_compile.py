"""Compile rehearsal: the main-path kernels and the one-shard serve step,
compiled for a described TPU v5e chip (none attached) at real widths.

Interpret mode cannot show what the chip's compiler refuses (tiling
rules, unsupported vector ops, VMEM and HBM limits); this does, at no
chip time.  The topology is described inside a module fixture, never
while a module is imported: one process at a time may load the TPU
library, and the test workers must all collect the same tests.  Keep
these tests in this one file, so that one worker loads the library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

W, T, F, B, N_BLOCKS, CHUNK = 128, 4, 4, 16, 256, 4   # websearch-rl widths
HBM_BYTES = 16 * 10**9                                # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, one_chip):
    from repro.kernels.block_scan.block_scan import block_scan_pallas
    from repro.kernels.block_scan.block_scan_pruned import (
        block_scan_pruned_chunk, block_scan_pruned_pallas, build_rule_meta,
    )

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    occ = sd((N_BLOCKS, T, F, W), jnp.uint32)
    if name == "block_scan_pallas":
        return (lambda o, a, r, p: block_scan_pallas(o, a, r, p, interpret=False),
                (occ, sd((T, F), jnp.bool_), sd((T,), jnp.bool_), sd((T,), jnp.bool_)))
    if name == "block_scan_pruned_pallas":
        allowed = np.zeros((T, F), bool)
        allowed[:, 1] = allowed[:, 3] = True           # mr_B-like: U|T planes
        required = np.array([1, 1, 0, 0], bool)
        present = np.array([1, 1, 1, 0], bool)
        return (lambda o: block_scan_pruned_pallas(o, allowed, required, present,
                                                   interpret=False), (occ,))

    def chunk(o, a, r, p, bp):
        meta = build_rule_meta(a, r, p, bp)
        return block_scan_pruned_chunk(o, meta, chunk=CHUNK, n_terms=T,
                                       interpret=False)

    return chunk, (sd((B, N_BLOCKS, T * F, W), jnp.uint32), sd((B, T, F), jnp.bool_),
                   sd((B, T), jnp.bool_), sd((B, T), jnp.bool_), sd((B,), jnp.int32))


@pytest.mark.parametrize("name", ["block_scan_pallas", "block_scan_pruned_pallas",
                                  "block_scan_pruned_chunk"])
def test_scan_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["pallas_block_scan", "xla"])
def test_one_shard_serve_step_fits_v5e(one_chip, backend, monkeypatch):
    """The websearch-rl serve step over one index shard (256 blocks of
    4096 documents) at a query bucket of 16 compiles for one v5e and
    fits its HBM."""
    import repro.kernels.block_scan.block_scan_pruned as pruned
    from repro.configs.websearch_rl import WebSearchCfg
    from repro.launch.steps import build_cell

    # The default backend here is the CPU, which would pick interpret
    # mode; the chip never does.
    monkeypatch.setattr(pruned, "resolve_interpret", lambda interpret: False)
    cell = build_cell("websearch-rl", "serve_queries", cfg_override=WebSearchCfg(
        n_blocks=N_BLOCKS, block_docs=32 * W, backend=backend))

    def sd(a, lead=None):
        shape = a.shape if lead is None else (lead,) + a.shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    qt, bins, occ, scores, tp = cell.args
    args = (sd(qt), jax.tree_util.tree_map(sd, bins), sd(occ, B), sd(scores, B),
            sd(tp, B))
    compiled = jax.jit(cell.fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas_block_scan")
