"""``ssa-fused-packed`` backend: fused SSA decode over uint32 KV bit-planes.

The decode hot loop of the packed spiking KV cache: cached K/V spike planes
(packed at insert time, 1 bit/spike in HBM) flow into the packed Pallas
kernel *as words* — they are never unpacked in XLA; the kernel expands them
to MXU lanes per-tile in VMEM.  The planes stay at KV-head granularity:
the kernel tiles each KV head's group of query heads together and reads
its words once.  Only the single new query token is encoded and packed per
step.  Outputs are bit-identical to ``ssa-fused`` /
``ssa-xla`` for the same seeds and positions (shared tile body + counter
RNG), and since the streams are position-keyed the gathered cache span may
be anything that covers the written tokens (extent-bounded paged decode).

Inference-only, like the packed kernel itself; training and prefill route
through ``ssa-fused`` on dense trains.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels.ssa_attention.ops import ssa_attention as fused_ssa_attention

from .base import AttentionInvocation, register_backend
from .spiking import head_sharded, packed_fused_decode

__all__ = ["SsaFusedPackedBackend"]


class SsaFusedPackedBackend:
    name = "ssa-fused-packed"

    def supports(self, a, mode: str) -> bool:
        return a.impl == "ssa" and a.spike_storage == "packed" and mode == "decode"

    @head_sharded
    def apply(self, inv: AttentionInvocation, head0) -> jnp.ndarray:
        return packed_fused_decode(
            inv, head0, functools.partial(fused_ssa_attention, packed=True),
            self.name,
        )


register_backend(SsaFusedPackedBackend())
