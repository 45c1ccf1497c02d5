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

from .base import AttentionInvocation, register_backend
from .spiking import head_sharded, packed_fused_decode

__all__ = ["SdsaFusedPackedBackend"]


class SdsaFusedPackedBackend:
    name = "sdsa-fused-packed"

    def supports(self, a, mode: str) -> bool:
        return (
            a.impl == "sdsa" and a.spike_storage == "packed" and mode == "decode"
        )

    @head_sharded
    def apply(self, inv: AttentionInvocation, head0) -> jnp.ndarray:
        return packed_fused_decode(inv, head0, fused_sdsa_attention, self.name)


register_backend(SdsaFusedPackedBackend())
