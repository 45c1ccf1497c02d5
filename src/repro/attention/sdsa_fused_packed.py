"""``sdsa-fused-packed`` backend: fused SDSA decode over uint32 KV planes.

The addition-only decode hot loop: cached K/V spike planes stay packed all
the way into the Pallas kernel, where ``k AND v`` happens on the words
themselves (one uint32 op per 32 channels) before the per-tile VMEM unpack
— ``unpack_spikes`` never appears in the decode HLO.  Only the single new
query token is encoded and packed per step, and the query gate applies at
finalize inside the kernel.  Outputs are bit-identical to ``sdsa-xla`` for
the same seeds and positions (shared counter RNG under ``SALT_SDSA``), so
the extent-bounded paged gather, migration, prefix sharing and speculative
verification all compose unchanged.

Inference-only, like the packed kernel itself; training and prefill route
through ``sdsa-xla`` on dense trains.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssa_attention.ops import sdsa_attention as fused_sdsa_attention

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

__all__ = ["SdsaFusedPackedBackend"]


class SdsaFusedPackedBackend:
    name = "sdsa-fused-packed"

    def supports(self, a, mode: str) -> bool:
        return (
            a.impl == "sdsa" and a.spike_storage == "packed" and mode == "decode"
        )

    @head_sharded
    def apply(self, inv: AttentionInvocation, head0) -> jnp.ndarray:
        from repro.bitpack import pack_spikes

        if inv.packed_k is None or inv.packed_v is None:
            raise ValueError("sdsa-fused-packed requires packed KV planes")
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
            fused_sdsa_attention(
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


register_backend(SdsaFusedPackedBackend())
