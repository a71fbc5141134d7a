"""Pallas TPU kernels (validated with interpret=True on CPU).

The paper's compute hot-spot IS the kernel story: latency-constrained
recurrent matvecs with fused gate epilogues. Each kernel is a subpackage:
``kernel.py`` (pl.pallas_call + explicit BlockSpec VMEM tiling),
``ops.py`` (jit'd public wrapper), ``ref.py`` (pure-jnp oracle).

The block-layout rules the recurrent kernels share live here. Mosaic, the
TPU kernel compiler, accepts a block only if its last two dims are
multiples of (8, 128) or equal to the array's own; interpret mode checks
neither, so both rules below are pinned by compile-only tests against a
described v5e chip (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


@functools.cache
def on_cpu() -> bool:
    """True when the default backend is CPU -> kernels run interpret=True."""
    return jax.default_backend() == "cpu"


def pick_batch_block(B: int, limit: int = 256) -> int:
    """Rows per batch tile of a decode grid: the whole batch when it fits
    ``limit``, else the largest multiple-of-8 divisor of ``B`` up to
    ``limit``, else the whole batch. Never a block Mosaic refuses (a tile's
    row count must be a multiple of 8 or the whole batch)."""
    if B <= limit:
        return B
    for blk in range(limit - limit % 8, 0, -8):
        if B % blk == 0:
            return blk
    return B


def step_mask(mask: Optional[jax.Array], T: int, B: int) -> jax.Array:
    """(T, B) length mask (None = every step live) -> the (T, B, 1) float32
    array the sequence kernels stream one :func:`step_mask_spec` block per
    grid step. Its (B, 1) trailing block equals the array's own dims, so
    Mosaic takes it at any B; a (1, B) block of a (T, B) array is refused.
    Unmasked calls stream all-ones, so masked and unmasked prefill run one
    kernel program and stay bitwise-equal on live rows."""
    if mask is None:
        return jnp.ones((T, B, 1), jnp.float32)
    return mask.astype(jnp.float32)[..., None]


def step_mask_spec(B: int) -> pl.BlockSpec:
    """Grid step ``t``'s (1, B, 1) slice of a :func:`step_mask` array."""
    return pl.BlockSpec((1, B, 1), lambda t: (t, 0, 0))
