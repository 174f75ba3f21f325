"""Bitpacked block store for query-time index scanning.

The inverted index is consumed at query time as a *bitpacked occupancy
tensor*::

    occ[block, term, field, word]  (uint32)

bit ``j`` of ``occ[b, t, f, w]`` says whether document ``b*BLOCK_DOCS +
w*32 + j`` contains query term ``t`` in field ``f``.  Documents are laid
out in static-rank order, so scanning blocks in order means scanning the
index best-first — exactly the layout the paper assumes ("the index is
sorted by static rank").

A *block* is the unit of the paper's ``u`` accumulator (index blocks
read from disk).  On TPU the analogue is one HBM→VMEM tile of the
occupancy tensor; the cost model charges one unit of ``u`` per
``(term, field)`` plane a match rule actually inspects in a block (a
rule that looks at fewer fields reads fewer posting blocks).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

WORD_BITS = 32

__all__ = [
    "WORD_BITS",
    "pack_bits",
    "unpack_bits",
    "popcount",
    "doc_bit",
    "words_per_block",
]


def words_per_block(block_docs: int) -> int:
    if block_docs % WORD_BITS != 0:
        raise ValueError(f"block_docs must be a multiple of {WORD_BITS}")
    return block_docs // WORD_BITS


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """Pack a boolean array (..., n_docs) into uint32 words (..., n_docs/32).

    Bit ``j`` of word ``w`` corresponds to doc ``w*32 + j`` (LSB-first).
    Host-side (numpy) — used by the index builder.  Returns a
    C-contiguous native-order array.
    """
    bits = np.asarray(bits, dtype=bool)
    n = bits.shape[-1]
    if n % WORD_BITS != 0:
        raise ValueError(f"trailing dim must be a multiple of {WORD_BITS}")
    # Little bit order puts doc w*32 + j at bit j%8 of byte j//8;
    # reading the four bytes little-endian makes that bit j of word w.
    # packbits keeps a strided input's layout, so make the bytes
    # contiguous (a no-op for the builder's C-ordered planes) to view.
    packed = np.ascontiguousarray(np.packbits(bits, axis=-1, bitorder="little"))
    return packed.view("<u4").astype(np.uint32, copy=False)


def unpack_bits(words: np.ndarray) -> np.ndarray:
    """Inverse of :func:`pack_bits` (host-side)."""
    words = np.asarray(words, dtype=np.uint32)
    shifts = np.arange(WORD_BITS, dtype=np.uint32)
    bits = (words[..., None] >> shifts) & np.uint32(1)
    return bits.astype(bool).reshape(*words.shape[:-1], words.shape[-1] * WORD_BITS)


def popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Per-word population count; device-side."""
    return lax.population_count(x)


def doc_bit(words: jnp.ndarray, doc_in_block: jnp.ndarray) -> jnp.ndarray:
    """Extract the bit for a document offset inside a block of words.

    ``words``: (..., W) uint32; ``doc_in_block``: scalar/vector int index.
    """
    w = doc_in_block // WORD_BITS
    b = doc_in_block % WORD_BITS
    return (jnp.take(words, w, axis=-1) >> b.astype(jnp.uint32)) & jnp.uint32(1)
