"""Plain jnp oracle of the grouped expert matmul."""
from __future__ import annotations

import jax.numpy as jnp


def moe_gmm_ref(lhs, rhs, group_sizes, *, out_dtype=None):
    """Each row times its expert's weights, via a per-row expert id
    (rows past ``sum(group_sizes)`` come out 0)."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(m)
    expert = jnp.searchsorted(ends, row, side="right")
    live = row < ends[-1]
    w = rhs[jnp.minimum(expert, rhs.shape[0] - 1)]
    out = jnp.einsum("mk,mkn->mn", lhs, w, preferred_element_type=jnp.float32)
    out = jnp.where(live[:, None], out, 0.0)
    return out.astype(lhs.dtype if out_dtype is None else out_dtype)
