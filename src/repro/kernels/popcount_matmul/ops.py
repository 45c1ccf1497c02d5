"""Jitted public wrapper for the popcount-matmul kernel.

Pads packed operands to tile boundaries (zero pad words contribute zero
counts — pack_spikes guarantees pad bits are 0) and dispatches the Pallas
kernel; leading batch dims are vmapped.  No VJP: counts are integer-valued
spike statistics consumed by sampling stages, not a differentiable path
(the trainable SSA route keeps the dense STE kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs import trace_scope

from ..common import cdiv
from .kernel import build_popcount_matmul_pallas

__all__ = ["popcount_matmul"]


def _pad2(x, rows_to, cols_to):
    r, c = x.shape
    if r == rows_to and c == cols_to:
        return x
    return jnp.pad(x, ((0, rows_to - r), (0, cols_to - c)))


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_w", "interpret")
)
def popcount_matmul(
    a_packed: jax.Array,
    b_packed: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_w: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """AND-popcount contraction on packed spike words.

    a_packed: (..., M, W) uint32; b_packed: (..., N, W) uint32.
    Returns (..., M, N) int32 counts, bit-exact vs
    ``repro.bitpack.popcount_matmul_ref`` (and vs the dense 0/1 einsum).
    """
    if a_packed.shape[-1] != b_packed.shape[-1]:
        raise ValueError(
            f"word counts differ: {a_packed.shape[-1]} vs {b_packed.shape[-1]}"
        )
    if a_packed.ndim > 2 or b_packed.ndim > 2:
        # match popcount_matmul_ref's broadcasting over leading batch dims
        batch = jnp.broadcast_shapes(a_packed.shape[:-2], b_packed.shape[:-2])
        a_flat = jnp.broadcast_to(a_packed, batch + a_packed.shape[-2:]).reshape(
            (-1,) + a_packed.shape[-2:]
        )
        b_flat = jnp.broadcast_to(b_packed, batch + b_packed.shape[-2:]).reshape(
            (-1,) + b_packed.shape[-2:]
        )
        fn = functools.partial(
            popcount_matmul,
            block_m=block_m,
            block_n=block_n,
            block_w=block_w,
            interpret=interpret,
        )
        out = jax.vmap(fn)(a_flat, b_flat)
        return out.reshape(batch + out.shape[-2:])

    m, w = a_packed.shape
    n = b_packed.shape[0]
    # Mosaic tiles the word (lane) axis only in multiples of 128 words; a
    # width that fits one tile is taken whole (block == full array dim).
    block_w = w if w <= block_w else cdiv(block_w, 128) * 128
    m_pad = cdiv(m, block_m) * block_m
    n_pad = cdiv(n, block_n) * block_n
    w_pad = cdiv(w, block_w) * block_w
    call = build_popcount_matmul_pallas(
        m_pad=m_pad,
        n_pad=n_pad,
        w_pad=w_pad,
        block_m=block_m,
        block_n=block_n,
        block_w=block_w,
        interpret=interpret,
    )
    with trace_scope("repro/kernels/popcount_matmul"):
        out = call(_pad2(a_packed, m_pad, w_pad), _pad2(b_packed, n_pad, w_pad))
    return out[:m, :n]
