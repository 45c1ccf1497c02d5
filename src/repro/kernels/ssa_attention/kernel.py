"""Fused stochastic-spiking-attention Pallas TPU kernel.

TPU realisation of the SAU-array dataflow (paper Fig. 2/3, DESIGN.md §2):

  * the `N x N` SAU array          -> MXU tiles of a 0/1 matmul (`block_q x
    block_k` per grid step); the AND+counter column of each SAU is one lane
    of the dot product (0/1 operands => dot == popcount of AND);
  * "no intermediate DRAM traffic" -> flash-attention-style fusion: the score
    tile `S` is Bernoulli-sampled in VMEM/registers and immediately consumed
    against the streamed `V` tile; `S` never reaches HBM;
  * per-encoder LFSR PRNGs         -> stateless counter RNG keyed on the
    tokens' *absolute positions* (request-addressed, RNG contract v2): the
    draw for a (query, key) pair or a (query, channel) lane is identical
    whatever the batch row, tile geometry, cache extent or decode width, so
    tiling, remat, the backward pass — and the serving scheduler moving a
    request between rows or gather spans — regenerate identical bits
    (`kernels.common.uniform_from_counter`);
  * power-of-two normalisation     -> probabilities stay as raw counts and
    are compared against `u * D_K` / `u * visible` — no division on the
    sampling path, mirroring the shift-free hardware comparison.

Operands beyond Q/K/V: a per-row uint32 seed vector (one stream per
batch/head row) and per-row absolute position vectors for queries and keys.
Position ``-1`` marks absent tokens (prefill padding, never-written cache
rows); they are masked out of eq. 5 and excluded from the eq. 6 ``visible``
normaliser, which the kernel accumulates across kv tiles in scratch.

Grid: ``(B, num_q_tiles, num_kv_tiles)`` with the kv axis innermost
(reduction).  The attention-count accumulator lives in a VMEM scratch tile
and is sampled into output spikes when the last kv tile retires.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv, uniform_from_counter, unpack_words_to_lanes

import numpy as np

# Salts decorrelating the two Bernoulli encoder banks (eq. 5 vs eq. 6).
# (numpy scalars => jaxpr literals, safe to close over in kernel bodies)
SALT_S = np.uint32(0x9E3779B9)
SALT_A = np.uint32(0x85EBCA6B)

# Salts for the addition-only backend families (same counter scheme, one
# independent Bernoulli bank per draw site).  SDSA (spike-driven (k AND v)
# column-sum, arXiv 2307.01694) draws only an output bank; QKsum (token-sum
# QK scoring, arXiv 2503.00226) draws a score bank and an output bank.
SALT_SDSA = np.uint32(0x27D4EB2F)
SALT_QKSUM_S = np.uint32(0x94D049BB)
SALT_QKSUM_A = np.uint32(0xBF58476D)

# Fixed position strides of the request-addressed counter scheme (RNG
# contract v2): counter = qpos * STRIDE + (kpos | channel), uint32 wrap.
# Odd constants so the per-query stream origins decorrelate under the
# splitmix32 finaliser; *never* derived from shapes — that would re-couple
# the stream to geometry.
POS_STRIDE_S = np.uint32(0x9E3779B1)
POS_STRIDE_A = np.uint32(0x85EBCA77)


def _ssa_tile_body(
    seed_ref,
    qpos_ref,
    kvpos_ref,
    out_ref,
    acc_ref,
    vis_ref,
    q,              # (block_q, d_pad) f32 0/1 tile
    k,              # (block_k, d_pad) f32 0/1 tile
    v,              # (block_k, d_pad) f32 0/1 tile
    *,
    block_q: int,
    block_k: int,
    d_pad: int,
    d_k: int,
    causal: bool,
    window: Optional[int],
    num_kv_tiles: int,
):
    """Shared eq. 5/6 tile math: the dense and packed kernels differ only in
    how the Q/K/V tiles reach VMEM (f32 lanes vs uint32 words unpacked here);
    everything downstream — counts, masks, counter-RNG indices — is identical,
    which is what makes the packed path bit-exact vs the dense one."""
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        vis_ref[...] = jnp.zeros_like(vis_ref)

    # ---- eq. 5 tile: counts = Q-tile @ K-tile^T  (popcount of AND) --------
    counts_s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (block_q, block_k)

    # absolute token positions of this tile (operands, not iota: the stream
    # is keyed by content position, not by slab index)
    qp = qpos_ref[0]                   # (block_q, 1) int32
    kp = kvpos_ref[0]                  # (1, block_k) int32

    valid = (kp >= 0) & (qp >= 0)      # (block_q, block_k)
    if causal:
        valid &= kp <= qp
    if window is not None:
        valid &= kp > qp - window

    # Bernoulli encoder bank #1 — hardware compares count against u * D_K
    # (shift-free for power-of-two D_K); masked lanes compare against -1.
    qp_u = jnp.maximum(qp, 0).astype(jnp.uint32)
    kp_u = jnp.maximum(kp, 0).astype(jnp.uint32)
    idx_s = qp_u * POS_STRIDE_S + kp_u
    u_s = uniform_from_counter(seed_ref[b, 0] ^ SALT_S, idx_s)
    s = (valid & (u_s * jnp.float32(d_k) < counts_s)).astype(jnp.float32)

    # ---- eq. 6 partials: acc += S-tile @ V-tile; vis += |valid| -----------
    acc_ref[...] += jax.lax.dot_general(
        s, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    vis_ref[...] += jnp.sum(valid.astype(jnp.float32), axis=1, keepdims=True)

    # ---- final kv tile: Bernoulli encoder bank #2 -------------------------
    @pl.when(ik == num_kv_tiles - 1)
    def _finalize():
        col = jax.lax.broadcasted_iota(jnp.uint32, (block_q, d_pad), 1)
        idx_a = qp_u * POS_STRIDE_A + col
        u_a = uniform_from_counter(seed_ref[b, 0] ^ SALT_A, idx_a)
        visible = jnp.maximum(vis_ref[...], 1.0)        # (block_q, 1)
        out = (u_a * visible < acc_ref[...]).astype(out_ref.dtype)
        out_ref[0] = out


def _ssa_kernel(
    seed_ref, qpos_ref, kvpos_ref, q_ref, k_ref, v_ref, out_ref,
    acc_ref, vis_ref, **geom,
):
    """Dense entry point: Q/K/V tiles arrive as 0/1 lanes."""
    _ssa_tile_body(
        seed_ref,
        qpos_ref,
        kvpos_ref,
        out_ref,
        acc_ref,
        vis_ref,
        q_ref[0].astype(jnp.float32),
        k_ref[0].astype(jnp.float32),
        v_ref[0].astype(jnp.float32),
        **geom,
    )


def _ssa_kernel_packed(
    seed_ref, qpos_ref, kvpos_ref, q_ref, k_ref, v_ref, out_ref,
    acc_ref, vis_ref, **geom,
):
    """Packed entry point: tiles arrive as uint32 words (1 bit/spike in HBM)
    and expand to MXU lanes only here, in VMEM.  w_pad * 32 == d_pad, so the
    unpacked tiles have exactly the dense kernel's geometry and the shared
    body (same counter-RNG indices) produces bit-identical spikes."""
    _ssa_tile_body(
        seed_ref,
        qpos_ref,
        kvpos_ref,
        out_ref,
        acc_ref,
        vis_ref,
        unpack_words_to_lanes(q_ref[0]),
        unpack_words_to_lanes(k_ref[0]),
        unpack_words_to_lanes(v_ref[0]),
        **geom,
    )


def _sdsa_kernel_packed(
    seed_ref, qpos_ref, kvpos_ref, q_ref, k_ref, v_ref, out_ref,
    acc_ref, vis_ref, *,
    block_q: int,
    block_k: int,
    d_pad: int,
    d_k: int,
    causal: bool,
    window: Optional[int],
    num_kv_tiles: int,
):
    """Addition-only spike-driven attention (SDSA) over packed bit-planes.

    Score semantics replace the eq. 5 stochastic dot product with a
    mask-and-sum linear form: ``kv = k AND v`` is one uint32 word-AND per 32
    channels, the per-query count is a column sum of ``kv`` over *visible*
    keys (a 0/1 matmul against the valid mask, so it still rides the MXU),
    and the single Bernoulli bank re-binarises ``counts / visible`` with the
    division-free ``u * visible < counts`` comparison.  The query spike
    gates the output channel-wise (Q ⊗ SN(SUM(K ⊗ V)) — no multiplies
    anywhere on the value path).  Counter-RNG indices reuse the output-bank
    position stride, salted with ``SALT_SDSA`` so the stream is independent
    of the SSA banks while staying request-addressed (RNG contract v2).
    """
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        vis_ref[...] = jnp.zeros_like(vis_ref)

    # mask-and-sum tile: AND on words (32 spikes per op), unpack once per kv
    # tile in VMEM, column-sum over visible keys through the MXU
    kv = unpack_words_to_lanes(k_ref[0] & v_ref[0])     # (block_k, d_pad)

    qp = qpos_ref[0]                   # (block_q, 1) int32
    kp = kvpos_ref[0]                  # (1, block_k) int32
    valid = (kp >= 0) & (qp >= 0)
    if causal:
        valid &= kp <= qp
    if window is not None:
        valid &= kp > qp - window
    valid_f = valid.astype(jnp.float32)                 # (block_q, block_k)

    acc_ref[...] += jax.lax.dot_general(
        valid_f, kv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    vis_ref[...] += jnp.sum(valid_f, axis=1, keepdims=True)

    @pl.when(ik == num_kv_tiles - 1)
    def _finalize():
        qp_u = jnp.maximum(qp, 0).astype(jnp.uint32)
        col = jax.lax.broadcasted_iota(jnp.uint32, (block_q, d_pad), 1)
        idx = qp_u * POS_STRIDE_A + col
        u = uniform_from_counter(seed_ref[b, 0] ^ SALT_SDSA, idx)
        visible = jnp.maximum(vis_ref[...], 1.0)        # (block_q, 1)
        s = (u * visible < acc_ref[...]).astype(jnp.float32)
        q_lanes = unpack_words_to_lanes(q_ref[0])
        out_ref[0] = (q_lanes * s).astype(out_ref.dtype)


def build_ssa_pallas(
    *,
    bsz: int,
    n_q_pad: int,
    n_kv_pad: int,
    d_k: int,
    d_pad: int,
    out_dtype,
    causal: bool,
    window: Optional[int],
    block_q: int,
    block_k: int,
    interpret: bool,
    packed: bool = False,
    variant: str = "ssa",
):
    """Construct the pallas_call for a given padded geometry.

    Call signature: ``call(seeds, q_pos, kv_pos, q, k, v)`` with
    ``seeds (B, 1)`` uint32 in SMEM and positions as ``(B, n_q_pad, 1)`` /
    ``(B, 1, n_kv_pad)`` int32 (pad value -1 => masked).  ``packed=True``
    takes Q/K/V as uint32 bit-planes of width ``w_pad = d_pad // 32`` (see
    ``repro.bitpack``); output spikes stay dense — bit-identical to the
    dense kernel for the same seeds/positions.  ``variant="sdsa"`` swaps in
    the addition-only spike-driven tile body (packed operands only; same
    operand/grid signature, so the wrapper padding is shared)."""
    num_q_tiles = cdiv(n_q_pad, block_q)
    num_kv_tiles = cdiv(n_kv_pad, block_k)

    if variant == "ssa":
        body = _ssa_kernel_packed if packed else _ssa_kernel
    elif variant == "sdsa":
        if not packed:
            raise ValueError("the sdsa kernel variant is packed-only")
        body = _sdsa_kernel_packed
    else:
        raise ValueError(f"unknown kernel variant {variant!r}")

    kernel = functools.partial(
        body,
        block_q=block_q,
        block_k=block_k,
        d_pad=d_pad,
        d_k=d_k,
        causal=causal,
        window=window,
        num_kv_tiles=num_kv_tiles,
    )

    d_in = d_pad // 32 if packed else d_pad
    return pl.pallas_call(
        kernel,
        grid=(bsz, num_q_tiles, num_kv_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seeds (B, 1)
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec((1, block_q, d_in), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_in), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_in), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_q_pad, d_pad), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_pad), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )
