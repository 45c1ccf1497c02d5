"""``ssa-fused-packed`` backend: fused SSA decode over uint32 KV bit-planes.

The decode hot loop of the packed spiking KV cache: cached K/V spike planes
(packed at insert time, 1 bit/spike in HBM) flow into the packed Pallas
kernel *as words* — they are never unpacked in XLA; the kernel expands them
to MXU lanes per-tile in VMEM.  Only the single new query token is encoded
and packed per step.  Outputs are bit-identical to ``ssa-fused`` /
``ssa-xla`` for the same seeds and positions (shared tile body + counter
RNG), and since the streams are position-keyed the gathered cache span may
be anything that covers the written tokens (extent-bounded paged decode).

Inference-only, like the packed kernel itself; training and prefill route
through ``ssa-fused`` on dense trains.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssa_attention.ops import ssa_attention as fused_ssa_attention

from .base import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    AttentionInvocation,
    default_interpret,
    derive_step_row_seeds,
    fold_heads,
    register_backend,
)
from .spiking import folded_positions, head_sharded, rate_decode

__all__ = ["SsaFusedPackedBackend"]


class SsaFusedPackedBackend:
    name = "ssa-fused-packed"

    def supports(self, a, mode: str) -> bool:
        return a.impl == "ssa" and a.spike_storage == "packed" and mode == "decode"

    @head_sharded
    def apply(self, inv: AttentionInvocation, head0) -> jnp.ndarray:
        from repro.bitpack import pack_spikes

        if inv.packed_k is None or inv.packed_v is None:
            raise ValueError("ssa-fused-packed requires packed KV planes")
        hd = inv.q.shape[-1]
        # query spikes: encoded by the orchestration layer, packed here
        # (one token per step — negligible next to the cache read)
        qw = fold_heads(pack_spikes(inv.spike_q))      # (T, B*H, S_q, W)
        # cached planes: (B, S, T, H_kv, W) words -> folded (T, B*H, S, W);
        # GQA repeat happens on words (32 spikes per move)
        kw = jnp.moveaxis(inv.packed_k, 2, 0)
        vw = jnp.moveaxis(inv.packed_v, 2, 0)
        if inv.groups > 1:
            kw = jnp.repeat(kw, inv.groups, axis=3)
            vw = jnp.repeat(vw, inv.groups, axis=3)
        kw, vw = fold_heads(kw), fold_heads(vw)
        t_steps = qw.shape[0]
        b, h = inv.q.shape[0], inv.q.shape[2]
        seeds = inv.seeds if inv.seeds is not None else jnp.zeros(b, jnp.uint32)
        step_seeds = derive_step_row_seeds(seeds, t_steps, h, head0)
        q_pos, kv_pos = folded_positions(inv)
        interpret = default_interpret()
        outs = [
            fused_ssa_attention(
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
                packed=True,
                d_k=hd,
            )
            for t in range(t_steps)
        ]
        return rate_decode(jnp.stack(outs), b, h)


register_backend(SsaFusedPackedBackend())
