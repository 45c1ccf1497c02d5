"""Shared input prep for the spiking backends.

All three SSA backends and the Spikformer baseline consume per-time-step
spike matrices with heads folded into the batch axis.  This module turns an
:class:`~repro.attention.base.AttentionInvocation` into that layout — from
pre-encoded dense trains or, for the XLA fallback over a packed KV cache, by
unpacking the uint32 bit-planes (the fused packed backend never calls this
for K/V; it keeps the words packed all the way to VMEM).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .base import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    AttentionInvocation,
    default_interpret,
    derive_step_row_seeds,
    fold_heads,
)

__all__ = [
    "folded_spike_trains",
    "folded_positions",
    "head_sharded",
    "packed_fused_decode",
    "rate_decode",
]

# head axis of every per-head AttentionInvocation field
_HEAD_AXIS = {
    "q": 2, "k": 2, "v": 2,
    "spike_q": 3, "spike_k": 3, "spike_v": 3,
    "packed_k": 3, "packed_v": 3,
}
# per-sequence fields: identical on every head shard
_ROW_FIELDS = ("seeds", "q_positions", "kv_positions")


def head_sharded(apply_local):
    """Decorator for the Pallas backends' ``apply(self, inv, head0)``.

    XLA cannot partition a Mosaic kernel, so under an active sharding mesh
    (``distributed.sharding.current_rules``) the call runs inside
    ``shard_map``: one slice of heads per ``model`` shard when query and kv
    head counts divide it, else the whole call on every shard.
    ``apply_local`` gets the global index of its first head (``head0``),
    which keys the per-head RNG streams, so each shard samples exactly what
    the unsharded call samples for its heads.
    """

    @functools.wraps(apply_local)
    def apply(self, inv: AttentionInvocation) -> jax.Array:
        from repro.distributed.sharding import current_rules

        rules = current_rules()
        if rules is None:
            return apply_local(self, inv, 0)
        m = rules.model
        heads = inv.q.shape[2]
        split = m > 1 and heads % m == 0 and inv.a.num_kv_heads % m == 0
        arrays = {
            name: getattr(inv, name)
            for name in (*_HEAD_AXIS, *_ROW_FIELDS)
            if getattr(inv, name) is not None
        }

        def spec(name, x):
            if not split or name not in _HEAD_AXIS:
                return P()
            axes = [None] * x.ndim
            axes[_HEAD_AXIS[name]] = "model"
            return P(*axes)

        def body(local):
            head0 = jax.lax.axis_index("model") * (heads // m) if split else 0
            return apply_local(self, dataclasses.replace(inv, **local), head0)

        return jax.shard_map(
            body,
            mesh=rules.mesh,
            in_specs=({n: spec(n, x) for n, x in arrays.items()},),
            out_specs=P(None, None, "model", None) if split else P(),
            check_vma=False,
        )(arrays)

    return apply


def folded_positions(inv: AttentionInvocation, kv_heads=None):
    """(q_positions, kv_positions) repeated per folded head row.

    ``fold_heads`` lays rows out batch-major (row = b * H + h), so repeating
    each sequence's position vector H times yields the per-row positions the
    kernels and oracles consume.  Key positions repeat ``kv_heads`` times
    (default H, for K/V repeated to query heads).  Falls back to the
    contiguous default (``None``) when the orchestration layer provided no
    positions.
    """
    h = inv.q.shape[2]
    q_pos = kv_pos = None
    if inv.q_positions is not None:
        q_pos = jnp.repeat(jnp.asarray(inv.q_positions, jnp.int32), h, axis=0)
    if inv.kv_positions is not None:
        kv_pos = jnp.repeat(
            jnp.asarray(inv.kv_positions, jnp.int32), kv_heads or h, axis=0
        )
    return q_pos, kv_pos


def packed_fused_decode(inv: AttentionInvocation, head0, kernel, name: str):
    """Shared body of the packed fused backends.

    The query spikes are packed here (one token per step); the cached
    ``(B, S, T, H_kv, W)`` planes fold to ``(T, B*H_kv, S, W)`` and reach
    ``kernel`` at KV-head granularity, which tiles each KV head's query
    group together and reads its words once.  One kernel call per time
    step; seeds and query positions stay per query head row.
    """
    from repro.bitpack import pack_spikes

    if inv.packed_k is None or inv.packed_v is None:
        raise ValueError(f"{name} requires packed KV planes")
    hd = inv.q.shape[-1]
    qw = fold_heads(pack_spikes(inv.spike_q))          # (T, B*H, S_q, W)
    kw = fold_heads(jnp.moveaxis(inv.packed_k, 2, 0))  # (T, B*H_kv, S, W)
    vw = fold_heads(jnp.moveaxis(inv.packed_v, 2, 0))
    t_steps = qw.shape[0]
    b, h = inv.q.shape[0], inv.q.shape[2]
    seeds = inv.seeds if inv.seeds is not None else jnp.zeros(b, jnp.uint32)
    step_seeds = derive_step_row_seeds(seeds, t_steps, h, head0)
    q_pos, kv_pos = folded_positions(inv, kv_heads=inv.packed_k.shape[3])
    interpret = default_interpret()
    outs = [
        kernel(
            qw[t],
            kw[t],
            vw[t],
            step_seeds[t],
            inv.causal,
            inv.window,
            DEFAULT_BLOCK_Q,
            DEFAULT_BLOCK_K,
            interpret,
            q_positions=q_pos,
            kv_positions=kv_pos,
            d_k=hd,
        )
        for t in range(t_steps)
    ]
    return rate_decode(jnp.stack(outs), b, h)


def folded_spike_trains(inv: AttentionInvocation, *, unpack_kv: bool = True):
    """Returns (qs, ks, vs) as (T, B*H_pad, S, hd) 0/1 trains."""
    if inv.spike_q is None:
        raise ValueError("spiking backend invoked without spike_q train")
    qs = fold_heads(inv.spike_q)
    if inv.spike_k is not None:
        ks5, vs5 = inv.spike_k, inv.spike_v
    elif inv.packed_k is not None and unpack_kv:
        from repro.bitpack import unpack_spikes

        hd = inv.q.shape[-1]
        # (B, S, T, H_kv, W) planes -> (T, B, S, H_kv, hd) trains
        ks5 = jnp.moveaxis(unpack_spikes(inv.packed_k, hd), 2, 0)
        vs5 = jnp.moveaxis(unpack_spikes(inv.packed_v, hd), 2, 0)
    else:
        raise ValueError("spiking backend invoked without K/V spikes")
    if inv.groups > 1:
        # encode-then-repeat == repeat-then-encode (per-token encoder), so
        # GQA expansion on trains is exact
        ks5 = jnp.repeat(ks5, inv.groups, axis=3)
        vs5 = jnp.repeat(vs5, inv.groups, axis=3)
    return qs, fold_heads(ks5), fold_heads(vs5)


def rate_decode(spikes: jax.Array, b: int, h: int) -> jax.Array:
    """(T, B*H, S, hd) spike train -> (B, S, H, hd) f32 rates (mean over T)."""
    rate = spikes.astype(jnp.float32).mean(axis=0)
    bh, s, d = rate.shape
    return rate.reshape(b, h, s, d).transpose(0, 2, 1, 3)
