"""Attention backend protocol, registry, and selection rules.

The model layer (``models.blocks.attention_apply``) is thin orchestration:
projections, RoPE, KV-cache writes, and spike encoding.  Everything after
that — eq. 1 softmax, eq. 5/6 stochastic spiking attention, the Spikformer
baseline — is a registered :class:`AttentionBackend`, selected per call by
:func:`resolve_backend` from ``AttentionConfig.impl``/``.backend``/
``.spike_storage`` and the call mode.

Registered backends (see docs/attention_backends.md):

  * ``ann-xla``          — softmax attention (vanilla / flash-chunked XLA)
  * ``ssa-xla``          — eq. 5/6 in plain XLA with the fused kernel's
                           counter RNG (bit-identical to ``ssa-fused``)
  * ``ssa-fused``        — fused Pallas SSA kernel on dense spike lanes
  * ``ssa-fused-packed`` — fused Pallas SSA kernel reading uint32 bit-planes
                           (packed KV decode; no unpack in the hot loop)
  * ``spikformer-xla``   — Spikformer baseline [18]
  * ``sdsa-xla``         — addition-only spike-driven ``(k AND v)``
                           column-sum attention (arXiv 2307.01694)
  * ``sdsa-fused-packed``— fused SDSA over uint32 bit-planes (word-level
                           AND before the per-tile unpack; packed decode)
  * ``qksum-xla``        — addition-only token-sum QK scoring
                           (arXiv 2503.00226)

Seed derivation (RNG contract v2, "request-addressed"): backends receive a
per-sequence seed vector ``seeds (B,)`` uint32 (one value per batch row /
request) that the model layer has already folded per layer
(:func:`fold_layer_seeds`).  Each SSA backend expands it to one stream per
(row, head, time-step) with :func:`derive_step_row_seeds`, so the mapping
``(request seed, layer, head, t_step) -> stream`` is identical across
backends, trace-stable under scan/vmap, and reproducible between prefill
and decode.  Counter indices inside the streams are keyed by absolute token
position only (see ``kernels.ssa_attention.ref``), so nothing depends on
the batch row, pad bucket, cache extent, or decode width — same seeds =>
same spikes on every backend, in any batch geometry; that is what makes
backend choice a pure performance knob and gives the serving scheduler
vLLM-style freedom (row migration, extent-bounded gathers, prefix sharing).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Iterator, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionConfig
from repro.kernels.common import mix32

__all__ = [
    "MODES",
    "PAGE_ZERO",
    "PAGE_SCRATCH",
    "NUM_RESERVED_PAGES",
    "RNG_CONTRACT_VERSION",
    "AttentionInvocation",
    "AttentionBackend",
    "register_backend",
    "get_backend",
    "available_backends",
    "resolve_backend_name",
    "resolve_backend",
    "record_backends",
    "derive_request_seeds",
    "fold_layer_seeds",
    "derive_step_row_seeds",
    "fold_heads",
    "unfold_heads",
    "default_interpret",
    "is_paged_cache",
    "paged_extent",
    "gather_pages",
    "next_pow2",
    "bucketed_table_width",
]

# Version of the (seed, layer, t_step, position, channel) -> uniform mapping.
# Bump whenever the derivation chain or counter-index scheme changes: spike
# streams are only reproducible across builds that agree on this number.
# v1 derived per-step seeds from a split PRNG key and strided counters by
# batch row and padded cache geometry; v2 (this) is request-addressed.
RNG_CONTRACT_VERSION = 2

MODES = ("train", "prefill", "decode")

# "decode" doubles as the **prefix-extend** mode: every registered backend
# accepts n_q > 1 queries against an already-written KV span (the chunked
# paged prefill writes a chunk of tokens through the block table and then
# attends over previous pages + the chunk itself in one call).  Causality
# inside the chunk needs no extra machinery — masks and SSA counter draws
# key off absolute positions, so a chunk samples exactly the spikes a
# one-shot prefill of the same tokens would.

# Default tile geometry for the fused kernels.  Since RNG contract v2 the
# counter streams are independent of tiling (position-keyed), so these are
# pure performance knobs — any block size samples the same spikes.  Packed
# calls take their tiles from the shapes (a KV-head group's query rows,
# capped at DEFAULT_BLOCK_Q; see kernels/ssa_attention/ops.py).
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


@dataclasses.dataclass
class AttentionInvocation:
    """Everything a backend may consume, prepared by the orchestration layer.

    Dense activations are post-RoPE; ``k``/``v`` stay at KV-head granularity
    (``groups`` = query heads per kv head; the XLA backends repeat them,
    the packed kernels tile each group's query heads against them).
    Spiking callers provide pre-encoded trains (``spike_*``, shape
    ``(T, B, S, H, hd)``) and/or packed uint32 bit-planes (``packed_*``,
    shape ``(B, S, T, H_kv, ceil(hd/32))`` — the packed KV-cache layout).
    Fields irrelevant to the selected backend stay ``None``.
    """

    a: AttentionConfig
    mode: str                                 # train | prefill | decode
    q: jax.Array                              # (B, S, H_pad, hd)
    k: Optional[jax.Array]                    # (B, S_kv, H_kv, hd)
    v: Optional[jax.Array]
    groups: int
    causal: bool
    window: Optional[int] = None
    softcap: Optional[float] = None
    # per-sequence uint32 seeds (B,), already folded per layer by the model
    # (fold_layer_seeds); the SSA sampling streams derive from these alone
    seeds: Optional[jax.Array] = None
    # absolute token positions: (B, S) for queries, (B, S_kv) for keys;
    # -1 marks absent tokens (pad rows, never-written cache slots).  Both
    # the ann mask and the SSA counter RNG key off these.
    kv_positions: Optional[jax.Array] = None
    q_positions: Optional[jax.Array] = None
    spike_q: Optional[jax.Array] = None       # (T, B, S, H_pad, hd)
    spike_k: Optional[jax.Array] = None       # (T, B, S_kv, H_kv, hd)
    spike_v: Optional[jax.Array] = None
    packed_k: Optional[jax.Array] = None      # (B, S_kv, T, H_kv, W) uint32
    packed_v: Optional[jax.Array] = None


@runtime_checkable
class AttentionBackend(Protocol):
    """One registered attention implementation."""

    name: str

    def supports(self, a: AttentionConfig, mode: str) -> bool:
        """Whether this backend can serve ``(config, mode)``."""
        ...

    def apply(self, inv: AttentionInvocation) -> jax.Array:
        """Run attention; returns real-valued (B, S, H_pad, hd) output
        (rate-decoded over T for spiking backends)."""
        ...


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, AttentionBackend] = {}


def register_backend(backend: AttentionBackend) -> AttentionBackend:
    """Register (or override) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> AttentionBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown attention backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def resolve_backend_name(
    a: AttentionConfig, mode: str, platform: Optional[str] = None
) -> str:
    """Map (config, mode, platform) -> backend name.

    ``a.backend``: ``"xla"`` forces the XLA reference implementations,
    ``"fused"`` forces the Pallas kernels (interpret-mode on CPU), ``"auto"``
    picks fused on TPU and XLA elsewhere.  With ``spike_storage="packed"``
    the fused decode path consumes the uint32 KV bit-planes directly
    (``ssa-fused-packed``); every other (impl, mode) cell has exactly one
    implementation per xla/fused choice.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    choice = getattr(a, "backend", "auto")
    if choice not in ("auto", "xla", "fused"):
        raise ValueError(
            f"attention.backend must be 'auto', 'xla' or 'fused', got {choice!r}"
        )
    if a.impl == "ann":
        if choice == "fused":
            raise ValueError(
                "attention.backend='fused' requires impl='ssa' (the fused "
                f"Pallas kernels implement stochastic spiking attention); "
                f"got impl={a.impl!r}"
            )
        return "ann-xla"
    if a.impl == "spikformer":
        if choice == "fused":
            raise ValueError(
                "attention.backend='fused' requires impl='ssa'; "
                f"got impl={a.impl!r}"
            )
        return "spikformer-xla"
    if a.impl == "qksum":
        if choice == "fused":
            raise ValueError(
                "attention.backend='fused' requires impl='ssa' or 'sdsa' "
                "(token-sum scoring has no fused kernel); "
                f"got impl={a.impl!r}"
            )
        return "qksum-xla"
    if a.impl == "sdsa":
        if platform is None:
            platform = jax.default_backend()
        use_fused = choice == "fused" or (choice == "auto" and platform == "tpu")
        # the only fused SDSA path is the packed decode kernel; every other
        # (mode, storage) cell falls back to the bit-identical XLA form, so
        # backend='fused' remains a valid whole-model setting
        if use_fused and mode == "decode" and a.spike_storage == "packed":
            return "sdsa-fused-packed"
        return "sdsa-xla"
    if a.impl != "ssa":
        raise ValueError(f"unknown attention impl {a.impl!r}")
    if platform is None:
        platform = jax.default_backend()
    use_fused = choice == "fused" or (choice == "auto" and platform == "tpu")
    if not use_fused:
        return "ssa-xla"
    if mode == "decode" and a.spike_storage == "packed":
        return "ssa-fused-packed"
    return "ssa-fused"


_RESOLVED: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "repro_resolved_backends", default=None
)


@contextlib.contextmanager
def record_backends() -> Iterator[dict[str, set[str]]]:
    """Collect ``{mode: {backend name}}`` for every attention call traced
    inside the block — what the compiled programs actually dispatch to.
    Calls served from an already-compiled program are not traced again and
    so are not recorded."""
    rec: dict[str, set[str]] = {}
    token = _RESOLVED.set(rec)
    try:
        yield rec
    finally:
        _RESOLVED.reset(token)


def resolve_backend(
    a: AttentionConfig, mode: str, platform: Optional[str] = None
) -> AttentionBackend:
    name = resolve_backend_name(a, mode, platform)
    backend = get_backend(name)
    if not backend.supports(a, mode):
        raise ValueError(
            f"backend {name!r} does not support (impl={a.impl!r}, "
            f"mode={mode!r}, spike_storage={a.spike_storage!r})"
        )
    rec = _RESOLVED.get()
    if rec is not None:
        rec.setdefault(mode, set()).add(name)
    return backend


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


# Salts separating the seed-derivation stages (numpy scalars stay jaxpr
# literals).  Each stage ends in a mix32 avalanche, so streams from
# different (row, layer, head, step) coordinates are decorrelated.
_ROW_SALT = np.uint32(0x9E3779B9)
_LAYER_SALT = np.uint32(0x632BE5AB)
_HEAD_SALT = np.uint32(0x85EBCA6B)
_STEP_SALT = np.uint32(0xC2B2AE35)


def derive_request_seeds(rng: Optional[jax.Array], batch: int) -> jax.Array:
    """(B,) uint32 per-sequence seeds from a PRNG key (training/default path).

    Row ``b``'s seed is ``mix32(bits(rng) + b * SALT)`` — a pure function of
    ``(rng, b)`` that does NOT depend on the batch width, so the same
    logical sequence seeds identically whether it sits in a width-1 or
    width-64 batch.  Serving bypasses this and passes each request's own
    seed instead (``Request.seed``); the engine's default request seed is
    ``derive_request_seeds(None, 1)[0]``, which is what makes a request in
    any engine row match a manual batch-1 prefill+decode loop exactly.
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    base = jax.random.bits(rng, (), jnp.uint32)
    rows = jnp.arange(batch, dtype=jnp.uint32)
    return mix32(base + rows * _ROW_SALT)


def fold_layer_seeds(seeds: jax.Array, layer_index) -> jax.Array:
    """Fold a flat layer counter into the per-sequence seeds (elementwise).

    ``layer_index`` may be a traced scalar (the transformer scan carries it),
    so the fold is trace-stable and identical between prefill and decode —
    the property the serving cache-identity contract rests on.
    """
    li = jnp.asarray(layer_index).astype(jnp.uint32)
    return mix32(seeds.astype(jnp.uint32) ^ mix32(li * _LAYER_SALT + 1))


def derive_step_row_seeds(
    seeds: jax.Array, t_steps: int, heads: int, head0=0
) -> jax.Array:
    """(B,) layer seeds -> (T, B*heads) uint32 stream seeds, fold_heads order.

    One independent counter-RNG stream per (sequence, head, time step).  The
    single place this expansion lives: all SSA backends call it, which is
    what keeps xla / fused / fused-packed bit-identical for the same seeds.
    ``head0`` (may be traced) is the global index of the first of ``heads``
    heads — a head shard derives exactly its heads' streams.
    """
    h = jnp.asarray(head0).astype(jnp.uint32) + jnp.arange(heads, dtype=jnp.uint32)
    t = jnp.arange(t_steps, dtype=jnp.uint32)
    s = mix32(seeds.astype(jnp.uint32)[:, None] + h[None, :] * _HEAD_SALT)
    s = mix32(s[None] + t[:, None, None] * _STEP_SALT)        # (T, B, H)
    return s.reshape(t_steps, -1)


def fold_heads(z: jax.Array) -> jax.Array:
    """(T, B, S, H, hd) -> (T, B*H, S, hd): heads become batch rows (one
    counter-RNG stream per head)."""
    t, b, s, h, d = z.shape
    return z.transpose(0, 1, 3, 2, 4).reshape(t, b * h, s, d)


def unfold_heads(z: jax.Array, b: int, h: int) -> jax.Array:
    """(B*H, S, hd) -> (B, S, H, hd) (inverse of one fold_heads slice)."""
    bh, s, d = z.shape
    return z.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def default_interpret() -> bool:
    """Pallas kernels need interpret mode off-TPU (the CPU CI fallback)."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# paged decode dispatch
#
# With ``AttentionConfig.cache_layout="paged"`` the serving engine stores the
# KV cache as a shared page pool: every cache leaf is ``(num_pages,
# page_size, ...)`` (dense float k/v and packed uint32 ks/vs planes alike)
# and each slot dict carries a block table ``bt: (B, W)`` of page ids.  The
# helpers below reconstruct, per layer, the contiguous ``(B, S_cache, ...)``
# slab layout every registered backend already consumes — so all five
# backends work unchanged on paged caches, and the gathered buffer is
# bit-identical to what a slab cache would hold (the reserved zero page
# supplies the pristine init-fill rows for never-allocated table entries).
# ---------------------------------------------------------------------------

# Reserved page ids (the serving allocator never hands these out):
#   PAGE_ZERO    — immutable init-fill page; unallocated block-table entries
#                  point here so gathers see exactly the rows a fresh slab
#                  cache would hold (zeros / packed enc(0) / pos = -1).
#   PAGE_SCRATCH — garbage sink; inactive decode rows write (and gather)
#                  here, mirroring the slab engine's "idle slots decode
#                  garbage that is masked out" contract without ever
#                  corrupting the zero page.
PAGE_ZERO = 0
PAGE_SCRATCH = 1
NUM_RESERVED_PAGES = 2


def is_paged_cache(cache: Optional[dict]) -> bool:
    """A per-layer cache dict is paged iff it carries a block table."""
    return cache is not None and "bt" in cache


def paged_extent(cache: dict, layer_window: Optional[int]) -> int:
    """Logical contiguous extent a paged layer cache stands in for.

    Global layers: the block-table span ``W * page_size`` — the engine syncs
    a growth-bucketed table width for *every* impl (all backends are
    position-masked and extent-invariant since RNG contract v2, spiking
    included), so the span covers the allocated pages, not ``max_seq``.
    Sliding-window layers: clamped to the window, matching the slab
    layout's ``S_cache = min(window, max_seq)`` rolling extent.
    """
    page_size = cache["pos"].shape[-1]
    span = cache["bt"].shape[-1] * page_size
    return span if layer_window is None else min(layer_window, span)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1) — the one bucketing primitive
    behind prompt buckets, chunk buckets, and block-table widths."""
    p = 1
    while p < n:
        p <<= 1
    return p


def bucketed_table_width(rows: int, page_size: int, max_width: int) -> int:
    """Pow2-bucketed block-table width covering ``rows`` written cache rows.

    The single source of the growth-bucketing rule the serving engine uses
    both for its per-tick table sync and for chunked-prefill calls: every
    impl is extent-invariant (position-keyed RNG + position masks), so any
    span covering the written rows decodes identically and pow2 bucketing
    bounds recompiles by ``log2(max_width)``.
    """
    need = max(1, -(-max(rows, 1) // page_size))
    return min(next_pow2(need), max_width)


def gather_pages(pool: jax.Array, bt: jax.Array, extent: int) -> jax.Array:
    """Gather block-table pages into the contiguous slab layout.

    pool: ``(num_pages, page_size, ...)`` cache leaf; bt: ``(B, W)`` int32
    page ids.  Returns ``(B, extent, ...)`` — rows beyond a request's
    allocation come from the zero page and therefore equal the slab init
    fill bit-for-bit.
    """
    page_size = pool.shape[1]
    cols = -(-extent // page_size)
    g = jnp.take(pool, bt[:, :cols], axis=0)          # (B, cols, ps, ...)
    g = g.reshape((bt.shape[0], cols * page_size) + pool.shape[2:])
    return g[:, :extent]
