"""Public wrapper of the grouped expert matmul (``kernel.py``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.obs import trace_scope

from ..common import cdiv
from .kernel import KERNEL_NAME, build_moe_gmm_pallas

__all__ = ["moe_gmm", "gmm_tiles"]

# row tile: one step's routed pairs at decode (8 rows x top-8) and at a
# 16-token chunk fill at most one or two tiles, so each expert with rows
# is read once per step
ROW_TILE = 128
# largest (k tile x n tile) weight block, in elements (2 MiB in bf16;
# double-buffered it stays well inside the scoped VMEM)
MAX_BLOCK = 1 << 20


def _lane_tile(dim: int, cap: int) -> int:
    """Largest multiple of 128 dividing ``dim`` and at most ``cap``; the
    whole dimension where 128 does not divide it (small test widths)."""
    if dim % 128:
        return dim
    best = 128
    for t in range(128, min(dim, cap) + 1, 128):
        if dim % t == 0:
            best = t
    return best


def gmm_tiles(k: int, n: int) -> tuple[int, int]:
    """(tk, tn) of a ``(m, k) x (E, k, n)`` call: the widest n tile, then
    the widest k tile that keeps the weight block under ``MAX_BLOCK``."""
    tn = _lane_tile(n, 1152)
    tk = _lane_tile(k, max(128, MAX_BLOCK // tn))
    return tk, tn


def moe_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
            layer=None, out_dtype=None, interpret: bool = False) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[e]`` for the rows ``r`` expert ``e`` owns.

    ``lhs (m, k)``: rows sorted by expert, expert ``e`` owning the next
    ``group_sizes[e]`` rows; rows past ``sum(group_sizes)`` belong to no
    expert and come out 0.  ``rhs (E, k, n)``, or ``(L, E, k, n)`` stacked
    layers with ``layer`` (an int scalar, may be traced) picking one.
    Experts with no rows are skipped (their weights are not read).
    Float32 accumulation; the result is cast to ``out_dtype`` (default
    ``lhs.dtype``).
    """
    if layer is None:
        rhs = rhs[None]
        layer = 0
    m, k = lhs.shape
    _, num_experts, k2, n = rhs.shape
    if k2 != k or group_sizes.shape != (num_experts,):
        raise ValueError(
            f"moe_gmm shapes disagree: lhs {lhs.shape}, rhs {rhs.shape}, "
            f"group_sizes {group_sizes.shape}")
    out_dtype = lhs.dtype if out_dtype is None else out_dtype
    tm = ROW_TILE
    m_pad = cdiv(m, tm) * tm
    group_sizes = group_sizes.astype(jnp.int32)
    meta, visits = make_group_metadata(
        group_sizes=group_sizes, m=m_pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=num_experts,
        visit_empty_groups=False)
    tk, tn = gmm_tiles(k, n)
    call = build_moe_gmm_pallas(m=m_pad, k=k, n=n, tm=tm, tk=tk, tn=tn,
                                out_dtype=out_dtype, interpret=interpret)
    x = jnp.pad(lhs, ((0, m_pad - m), (0, 0))) if m_pad != m else lhs
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    with trace_scope(f"repro/kernels/{KERNEL_NAME}"):
        out = call(meta, visits, layer, x, rhs)
    rows = jnp.arange(m_pad, dtype=jnp.int32)[:, None]
    out = jnp.where(rows < jnp.sum(group_sizes), out, jnp.zeros((), out_dtype))
    return out[:m]
