"""Public jitted API for the fused SSA attention kernel.

`ssa_attention(...)` pads to tile boundaries, dispatches the Pallas kernel,
and installs a custom VJP: the backward pass *recomputes* the score spikes
``S`` from the stateless counter RNG (flash-attention-style memory saving —
S is never stored) and applies the straight-through estimator through both
Bernoulli encoders:

    dL/dV = S^T (g / vis)          dL/dS = (g / vis) V^T      (STE on eq. 6)
    dL/dQ = dL/dS K / D_K          dL/dK = dL/dS^T Q / D_K    (STE on eq. 5)

RNG contract v2 (request-addressed): ``seed`` may be a uint32 scalar (one
stream shared by every batch row) or a ``(B,)`` vector (one stream per
row), and draws are keyed by the tokens' absolute positions
(``q_positions`` / ``kv_positions``, default contiguous).  Padding inserted
here for tiling carries position ``-1`` and therefore never draws.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs import trace_scope

from ..common import cdiv, uniform_from_counter
from .kernel import SALT_S, build_ssa_pallas
from .ref import (
    ensure_positions,
    normalize_seed_positions,
    padded_dims,
    score_counter_idx,
    valid_mask,
    visible_counts,
)

__all__ = ["ssa_attention", "sdsa_attention"]


def _pad3(x, n_to, d_to):
    b, n, d = x.shape
    if n == n_to and d == d_to:
        return x
    return jnp.pad(x, ((0, 0), (0, n_to - n), (0, d_to - d)))


def _pad_pos(p, n_to):
    """Pad a (B, N) position vector to (B, n_to) with -1 (masked)."""
    b, n = p.shape
    if n == n_to:
        return p
    return jnp.pad(p, ((0, 0), (0, n_to - n)), constant_values=-1)


# single source of the seed-broadcast + default-position normalization
# (shared with the jnp oracle so every consumer stays byte-identical)
_norm_inputs = normalize_seed_positions


def _recompute_s(q, k, seeds, q_positions, kv_positions, causal, window):
    """Regenerate the score spikes S from the counter RNG (no storage)."""
    bsz, n_q, d_k = q.shape
    n_kv = k.shape[1]
    seeds, q_positions, kv_positions = _norm_inputs(
        seeds, q_positions, kv_positions, bsz, n_q, n_kv
    )
    counts_s = jnp.einsum(
        "bqd,bkd->bqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    valid = valid_mask(q_positions, kv_positions, causal, window)
    idx_s = score_counter_idx(q_positions, kv_positions)
    u_s = uniform_from_counter(seeds[:, None, None] ^ SALT_S, idx_s)
    return jnp.where(valid, u_s * jnp.float32(d_k) < counts_s, False).astype(
        jnp.float32
    )


def ssa_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seed: jax.Array,
    causal: bool = False,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    *,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    packed: bool = False,
    d_k: Optional[int] = None,
) -> jax.Array:
    """Fused SSA attention; dense spikes by default, bit-planes with
    ``packed=True``.

    Dense: q (B, N_q, D_K) 0/1 spikes, k/v (B, N_kv, D_K); differentiable
    (STE custom VJP).  ``seed``: uint32 scalar or (B,) per-row vector.
    ``q_positions``/``kv_positions``: (B, N) int32 absolute positions
    (default contiguous, queries at the end of the kv axis); position -1
    masks a token out of the scores and the visible count.  Packed: q/k/v
    are uint32 bit-planes of shape (B, N, ceil(D_K/32)) from
    ``repro.bitpack.pack_spikes`` and ``d_k`` must be given; HBM traffic is
    1 bit/spike, words unpack to MXU tiles in VMEM, and the output (dense
    0/1 spikes, (B, N_q, D_K)) is bit-identical to the dense path for the
    same seeds/positions.  Packed k/v (and ``kv_positions``) may have fewer
    rows than q, one per KV head: ``B_q = B_kv * groups`` query rows in
    ``fold_heads`` order, the ``groups`` heads of a KV head tiled together
    against its words, read once (``block_q`` then caps the query tile).
    The packed path is inference-only.
    """
    if not packed:
        return _ssa_attention_dense(
            q, k, v, seed, q_positions, kv_positions,
            causal, window, block_q, block_k, interpret,
        )
    return _packed_attention(
        "ssa", q, k, v, seed, causal, window, block_q, block_k, interpret,
        q_positions, kv_positions, d_k,
    )


def sdsa_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seed: jax.Array,
    causal: bool = False,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    *,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    d_k: Optional[int] = None,
) -> jax.Array:
    """Fused addition-only spike-driven attention over uint32 bit-planes.

    Operands, padding, seeds and positions behave exactly like the packed
    path of :func:`ssa_attention`; only the tile body differs — ``k AND v``
    happens on the words themselves (one op per 32 channels) before the
    per-tile unpack, the per-query count is a valid-mask matmul, and the
    single output Bernoulli bank is salted with ``SALT_SDSA``.  Bit-exact
    vs. ``ref.sdsa_reference``; inference-only (no VJP), like every packed
    path.
    """
    return _packed_attention(
        "sdsa", q, k, v, seed, causal, window, block_q, block_k, interpret,
        q_positions, kv_positions, d_k,
    )


def _packed_tiles(rows: int, n_kv: int, block_q: int, block_k: int):
    """Tile geometry of a packed call, from its shapes.

    The query tile holds ``rows`` (a KV-head group's heads x tokens) padded
    to the 8-row sublane tile, up to ``block_q`` rows and tiled beyond.  The
    key axis pads to ``block_k`` and its tile spans up to four such blocks
    where they divide it: with only a few query rows a tile, narrow kv
    tiles would leave the grid mostly step overhead.
    Returns ``(block_q, rows_pad, block_k, n_kv_pad)``."""
    block_q = min(block_q, cdiv(rows, 8) * 8)
    n_kv_pad = cdiv(n_kv, block_k) * block_k
    block_k = next(
        block_k * m for m in (4, 2, 1) if n_kv_pad % (block_k * m) == 0
    )
    return block_q, cdiv(rows, block_q) * block_q, block_k, n_kv_pad


def _packed_attention(variant, q, k, v, seed, causal, window,
                      block_q, block_k, interpret,
                      q_positions, kv_positions, d_k):
    """Shared packed-operand dispatch: validate bit-plane widths, tile the
    query rows of each KV-head group together, build the kernel variant.

    ``q`` has ``B_q = B_kv * groups`` rows in ``fold_heads`` order (row
    ``b * H + h`` reads K/V row ``b * H_kv + h // groups``); ``seed`` is a
    scalar or ``(B_q,)``, ``q_positions`` ``(B_q, N_q)``, ``kv_positions``
    ``(B_kv, N_kv)``.  The ``groups`` consecutive query rows of a K/V row
    become one ``(groups * N_q)``-row query tile against it."""
    if d_k is None:
        raise ValueError("packed=True requires d_k (unpadded feature size)")
    from repro.bitpack import packed_width

    for name, arr in (("q", q), ("k", k), ("v", v)):
        if arr.dtype != jnp.uint32:
            raise TypeError(
                f"packed {name} must be uint32 words, got {arr.dtype}"
            )
        if arr.shape[-1] != packed_width(d_k):
            raise ValueError(
                f"packed {name} width {arr.shape[-1]} inconsistent with "
                f"d_k={d_k} (expected {packed_width(d_k)})"
            )
    b_q, n_q, _ = q.shape
    b_kv, n_kv, _ = k.shape
    if v.shape != k.shape or b_q % b_kv:
        raise ValueError(
            f"packed k {k.shape} / v {v.shape} do not group q {q.shape}: "
            "k and v must match and divide the query rows"
        )
    rows = (b_q // b_kv) * n_q
    block_q, rows_pad, block_k, n_kv_pad = _packed_tiles(
        rows, n_kv, block_q, block_k
    )
    d_pad = cdiv(d_k, 128) * 128
    w_pad = d_pad // 32
    seeds = jnp.broadcast_to(jnp.asarray(seed, jnp.uint32).reshape(-1), (b_q,))
    q_pos, kv_pos = ensure_positions(q_positions, kv_positions, 1, n_q, n_kv)
    q_pos = jnp.broadcast_to(q_pos, (b_q, n_q))
    kv_pos = jnp.broadcast_to(kv_pos, (b_kv, n_kv))

    def grouped(x):     # (B_q, N_q, ...) -> (B_kv, groups * N_q, ...)
        return x.reshape(b_kv, rows, *x.shape[2:])

    row_seeds = grouped(jnp.broadcast_to(seeds[:, None], (b_q, n_q)))
    call = build_ssa_pallas(
        bsz=b_kv,
        n_q_pad=rows_pad,
        n_kv_pad=n_kv_pad,
        d_k=d_k,
        d_pad=d_pad,
        out_dtype=jnp.float32,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
        packed=True,
        variant=variant,
    )
    with trace_scope(f"repro/kernels/{variant}_attention"):
        out = call(
            _pad3(row_seeds[:, :, None], rows_pad, 1),
            _pad_pos(grouped(q_pos), rows_pad)[:, :, None],
            _pad_pos(kv_pos, n_kv_pad)[:, None, :],
            _pad3(grouped(q), rows_pad, w_pad),
            _pad3(k, n_kv_pad, w_pad),
            _pad3(v, n_kv_pad, w_pad),
        )
    return out[:, :rows, :d_k].reshape(b_q, n_q, d_k)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10)
)
def _ssa_attention_dense(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    seed: jax.Array,
    q_positions: Optional[jax.Array],
    kv_positions: Optional[jax.Array],
    causal: bool = False,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Dense fused SSA.  q: (B, N_q, D_K) 0/1 spikes; k/v: (B, N_kv, D_K).

    Returns (B, N_q, D_K) 0/1 spikes, bit-exact vs. `ref.ssa_reference`.
    """
    bsz, n_q, d_k = q.shape
    n_kv = k.shape[1]
    n_q_pad, n_kv_pad, d_pad = padded_dims(n_q, n_kv, d_k, block_q, block_k)
    seeds, q_pos, kv_pos = _norm_inputs(
        seed, q_positions, kv_positions, bsz, n_q, n_kv
    )
    qp = _pad3(q, n_q_pad, d_pad)
    kp = _pad3(k, n_kv_pad, d_pad)
    vp = _pad3(v, n_kv_pad, d_pad)
    call = build_ssa_pallas(
        bsz=bsz,
        n_q_pad=n_q_pad,
        n_kv_pad=n_kv_pad,
        d_k=d_k,
        d_pad=d_pad,
        out_dtype=q.dtype,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    with trace_scope("repro/kernels/ssa_attention"):
        out = call(
            seeds.reshape(bsz, 1),
            _pad_pos(q_pos, n_q_pad)[:, :, None],
            _pad_pos(kv_pos, n_kv_pad)[:, None, :],
            qp,
            kp,
            vp,
        )
    return out[:, :n_q, :d_k]


def _ssa_fwd(q, k, v, seed, q_positions, kv_positions,
             causal, window, block_q, block_k, interpret):
    out = _ssa_attention_dense(
        q, k, v, seed, q_positions, kv_positions,
        causal, window, block_q, block_k, interpret,
    )
    return out, (q, k, v, seed, q_positions, kv_positions)


def _int_zero_cotangent(x):
    import numpy as np

    return np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)


def _ssa_bwd(causal, window, block_q, block_k, interpret, res, g):
    q, k, v, seed, q_positions, kv_positions = res
    bsz, n_q, d_k = q.shape
    n_kv = k.shape[1]
    s = _recompute_s(q, k, seed, q_positions, kv_positions, causal, window)
    _, q_pos, kv_pos = _norm_inputs(
        seed, q_positions, kv_positions, bsz, n_q, n_kv
    )
    vis = visible_counts(valid_mask(q_pos, kv_pos, causal, window))[:, :, None]
    g32 = g.astype(jnp.float32) / vis
    # STE through eq. 6
    dv = jnp.einsum("bqk,bqd->bkd", s, g32)
    ds = jnp.einsum("bqd,bkd->bqk", g32, v.astype(jnp.float32))
    # STE through eq. 5
    ds = ds / jnp.float32(d_k)
    dq = jnp.einsum("bqk,bkd->bqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
    # integer-typed operands (seed, positions) -> symbolic-zero cotangents
    dpos_q = None if q_positions is None else _int_zero_cotangent(q_positions)
    dpos_kv = None if kv_positions is None else _int_zero_cotangent(kv_positions)
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        _int_zero_cotangent(seed),
        dpos_q,
        dpos_kv,
    )


_ssa_attention_dense.defvjp(_ssa_fwd, _ssa_bwd)
