"""Shared kernel utilities.

Kernels TARGET TPU (BlockSpec/VMEM tiling, MXU-aligned shapes) and are
VALIDATED on CPU via ``interpret=True`` — the kernel body executes in
Python with the same block/grid semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["resolve_interpret", "pad_axis_to", "cdiv", "NEG_INF"]

NEG_INF = float("-inf")


def resolve_interpret(interpret: bool | None) -> bool:
    """Decide at call time whether a Pallas kernel runs in interpret mode.

    ``None`` compiles the kernel when a TPU is the default backend and
    interprets it anywhere else (the CPU test path).  Asking for
    interpret mode while a TPU is the default backend is an error: a
    kernel that silently ran in the interpreter on the chip would make
    every device measurement meaningless.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise RuntimeError(
            "Pallas interpret mode requested while a TPU is the default "
            "backend; pass interpret=None (or False) on the chip")
    return bool(interpret)


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_axis_to(x: jnp.ndarray, axis: int, multiple: int, value=0):
    """Pad ``axis`` of x up to the next multiple. Returns (padded, orig_len)."""
    n = x.shape[axis]
    target = cdiv(n, multiple) * multiple
    if target == n:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads, constant_values=value), n
