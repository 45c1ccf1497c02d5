"""Pallas TPU kernel: AND-popcount matmul over packed uint32 spike words.

Operands live in HBM as bit-planes (1 bit/spike — see ``repro.bitpack``);
each grid step DMAs a packed tile into VMEM, expands it to a 0/1 f32 MXU
tile *in VMEM* (never in HBM), and runs the contraction on the MXU: for 0/1
operands ``popcount(AND)`` == dot product, so the SAU column counters of the
paper map onto MXU lanes while HBM only ever sees packed words.

Grid: ``(num_m_tiles, num_n_tiles, num_w_tiles)`` with the word (reduction)
axis innermost; an f32 VMEM scratch tile accumulates partial counts across
word tiles.  Within a word tile the words expand ``CHUNK_W`` at a time, so
VMEM holds at most ``block_m x (CHUNK_W * 32)`` f32 lanes per operand —
(128, 512) f32, comfortably inside VMEM whatever the word tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv, unpack_words_to_lanes

__all__ = ["build_popcount_matmul_pallas"]

# Words unpacked per MXU contraction inside one word tile.
CHUNK_W = 16


def _popcount_matmul_kernel(
    a_ref,        # VMEM (block_m, block_w) uint32
    b_ref,        # VMEM (block_n, block_w) uint32
    out_ref,      # VMEM (block_m, block_n) int32
    acc_ref,      # VMEM scratch (block_m, block_n) f32
    *,
    num_w_tiles: int,
):
    iw = pl.program_id(2)

    @pl.when(iw == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # 0/1 operands: dot == popcount of AND; f32 accumulation is exact for
    # counts <= 2^24 (i.e. any realistic D_K / T product).  The word tile
    # unpacks CHUNK_W words at a time so VMEM holds at most
    # (block, CHUNK_W * 32) f32 lanes per operand.
    block_w = a_ref.shape[1]
    for c0 in range(0, block_w, CHUNK_W):
        c1 = min(c0 + CHUNK_W, block_w)
        a = unpack_words_to_lanes(a_ref[:, c0:c1])   # (block_m, (c1-c0)*32)
        b = unpack_words_to_lanes(b_ref[:, c0:c1])
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iw == num_w_tiles - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(jnp.int32)


def build_popcount_matmul_pallas(
    *,
    m_pad: int,
    n_pad: int,
    w_pad: int,
    block_m: int,
    block_n: int,
    block_w: int,
    interpret: bool,
):
    """pallas_call for packed (m_pad, w_pad) x (n_pad, w_pad) -> int32 counts."""
    num_w_tiles = cdiv(w_pad, block_w)
    kernel = functools.partial(_popcount_matmul_kernel, num_w_tiles=num_w_tiles)
    return pl.pallas_call(
        kernel,
        grid=(cdiv(m_pad, block_m), cdiv(n_pad, block_n), num_w_tiles),
        in_specs=[
            pl.BlockSpec((block_m, block_w), lambda i, j, w: (i, w)),
            pl.BlockSpec((block_n, block_w), lambda i, j, w: (j, w)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )
