"""Attention backend-dispatch subsystem.

``models`` and ``serving`` never call attention math directly: the
orchestration layer (projections / RoPE / cache writes / spike encoding)
builds an :class:`AttentionInvocation` and hands it to the backend that
:func:`resolve_backend` selects from ``AttentionConfig.impl`` /
``.backend`` / ``.spike_storage`` and the call mode.  The kernel ``ops``
modules are the backend implementations' only entry points.

Importing this package registers the built-in backends:
``ann-xla``, ``ssa-xla``, ``ssa-fused``, ``ssa-fused-packed``,
``spikformer-xla``, plus the addition-only family ``sdsa-xla``,
``sdsa-fused-packed``, ``qksum-xla`` (see docs/attention_backends.md).
"""
from .base import (
    MODES,
    NUM_RESERVED_PAGES,
    PAGE_SCRATCH,
    PAGE_ZERO,
    RNG_CONTRACT_VERSION,
    AttentionBackend,
    AttentionInvocation,
    available_backends,
    bucketed_table_width,
    default_interpret,
    derive_request_seeds,
    derive_step_row_seeds,
    fold_heads,
    fold_layer_seeds,
    gather_pages,
    get_backend,
    is_paged_cache,
    next_pow2,
    paged_extent,
    record_backends,
    register_backend,
    resolve_backend,
    resolve_backend_name,
    unfold_heads,
    window_ring,
)
from .encoding import spike_encode

# built-in backend registration (import side effect, order irrelevant)
from . import ann_xla as _ann_xla            # noqa: F401
from . import qksum_xla as _qksum_xla        # noqa: F401
from . import sdsa_fused_packed as _sdsa_fp  # noqa: F401
from . import sdsa_xla as _sdsa_xla          # noqa: F401
from . import spikformer_xla as _spikformer  # noqa: F401
from . import ssa_fused as _ssa_fused        # noqa: F401
from . import ssa_fused_packed as _ssa_fp    # noqa: F401
from . import ssa_xla as _ssa_xla            # noqa: F401

__all__ = [
    "MODES",
    "NUM_RESERVED_PAGES",
    "PAGE_SCRATCH",
    "PAGE_ZERO",
    "RNG_CONTRACT_VERSION",
    "AttentionBackend",
    "AttentionInvocation",
    "available_backends",
    "default_interpret",
    "derive_request_seeds",
    "derive_step_row_seeds",
    "fold_heads",
    "fold_layer_seeds",
    "gather_pages",
    "get_backend",
    "is_paged_cache",
    "paged_extent",
    "window_ring",
    "record_backends",
    "register_backend",
    "resolve_backend",
    "resolve_backend_name",
    "spike_encode",
    "unfold_heads",
]
