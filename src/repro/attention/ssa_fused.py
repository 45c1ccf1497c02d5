"""``ssa-fused`` backend: the fused Pallas SSA kernel on dense spike lanes.

One kernel launch per SSA time step (T is small and static); heads are
folded into the kernel batch axis and each (row, head, step) gets its own
counter-RNG stream seed (``derive_step_row_seeds``).  Differentiable (the
kernel installs an STE custom VJP), so this is the training-and-serving
fast path.  Off-TPU the kernel runs in interpret mode — slow, but
bit-identical, which is how the CPU CI lane exercises it.
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
    register_backend,
)
from .spiking import (
    folded_positions,
    folded_spike_trains,
    head_sharded,
    rate_decode,
)

__all__ = ["SsaFusedBackend"]


class SsaFusedBackend:
    name = "ssa-fused"

    def supports(self, a, mode: str) -> bool:
        return a.impl == "ssa"

    @head_sharded
    def apply(self, inv: AttentionInvocation, head0) -> jnp.ndarray:
        qs, ks, vs = folded_spike_trains(inv)
        t_steps = qs.shape[0]
        b, h = inv.q.shape[0], inv.q.shape[2]
        seeds = inv.seeds if inv.seeds is not None else jnp.zeros(b, jnp.uint32)
        step_seeds = derive_step_row_seeds(seeds, t_steps, h, head0)
        q_pos, kv_pos = folded_positions(inv)
        interpret = default_interpret()
        outs = [
            fused_ssa_attention(
                qs[t],
                ks[t],
                vs[t],
                step_seeds[t],
                inv.causal,
                inv.window,
                DEFAULT_BLOCK_Q,
                DEFAULT_BLOCK_K,
                interpret,
                q_positions=q_pos,
                kv_positions=kv_pos,
            )
            for t in range(t_steps)
        ]
        return rate_decode(jnp.stack(outs), b, h)


register_backend(SsaFusedBackend())
