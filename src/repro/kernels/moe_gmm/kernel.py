"""Grouped matmul over the experts that hold rows (the dropless expert layer).

``lhs (m, k)`` holds the routed (token, expert) pairs of a step sorted by
expert, so expert ``e`` owns the contiguous rows ``offsets[e]:offsets[e+1]``;
``rhs (L, E, k, n)`` holds every expert's weights of ``L`` stacked layers,
of which the scalar-prefetched ``layer`` is used: a scanned layer stack
hands the kernel its whole stacked weights, since slicing one layer out
would copy all of its experts into a temporary first.  The grid walks
``(n tile, active (expert, m tile) pair, k tile)``: an expert with no rows
is never visited, so its weights are never read, and an ``m`` tile that
straddles experts is visited once per expert in it, each visit storing only
its own rows.  The active-pair list and its length come from
``megablox.gmm.make_group_metadata`` (scalar-prefetched; the grid's middle
extent is that traced length).

Each stored row is ``lhs_row @ rhs[e]`` accumulated in float32 over the k
tiles in order, at a fixed ``(tm, tk, tn)`` tile whatever ``m`` is, so a
row's result depends on that row alone: never on which rows share its
step, tile or batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the custom call's name in HLO and in profiler traces
KERNEL_NAME = "moe_gmm"


def _gmm_kernel(meta, layer, lhs_ref, rhs_ref, out_ref, acc_ref, *, tm: int,
                tiles_k: int):
    del layer  # used by the index maps only
    offsets, group_ids, m_tile_ids = meta
    visit = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        g = group_ids[visit]
        row = m_tile_ids[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        out_ref[...] = jnp.where(
            mine, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def build_moe_gmm_pallas(*, m: int, k: int, n: int, tm: int, tk: int, tn: int,
                         out_dtype, interpret: bool):
    """``call(meta, num_visits, layer, lhs, rhs) -> (m, n)``; ``meta`` is
    the ``(offsets, group_ids, m_tile_ids)`` triple of
    ``make_group_metadata``, ``num_visits`` its active-pair count and
    ``layer`` an int32 ``(1,)`` index into ``rhs``'s leading axis."""
    tiles_k = k // tk
    kernel = functools.partial(_gmm_kernel, tm=tm, tiles_k=tiles_k)

    def call(meta, num_visits, layer, lhs, rhs):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                in_specs=[
                    pl.BlockSpec((tm, tk),
                                 lambda j, v, i, mt, ly: (mt[2][v], i)),
                    pl.BlockSpec((None, None, tk, tn),
                                 lambda j, v, i, mt, ly: (ly[0], mt[1][v], i, j)),
                ],
                out_specs=pl.BlockSpec((tm, tn),
                                       lambda j, v, i, mt, ly: (mt[2][v], j)),
                grid=(n // tn, num_visits, tiles_k),
                scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=interpret,
            name=KERNEL_NAME,
        )(meta, layer, lhs, rhs)

    return call
