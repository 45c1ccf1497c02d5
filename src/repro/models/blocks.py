"""Composable model building blocks (pure-functional, pytree params).

Everything the assigned architectures need: RMS/LayerNorm, RoPE (plain or
YaRN) / M-RoPE,
GQA attention with three interchangeable implementations (`ann` softmax /
`ssa` the paper's stochastic spiking attention / `spikformer` baseline) —
each realised by a backend from the `repro.attention` registry (XLA
reference or fused Pallas kernels, `AttentionConfig.backend`) —
SwiGLU/GeGLU/GELU MLPs, and MoE (shared + routed experts, top-k: dropless
grouped-matmul dispatch when serving, capacity dispatch in training).

Conventions:
  * params are nested dicts of jnp arrays; layer stacks add a leading L axis
    and are consumed by `jax.lax.scan`;
  * activations are (B, S, D); attention heads are folded as (B, S, H, hd);
  * every apply function is pure; RNG (for SSA sampling) comes in as a key.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.attention import (
    AttentionInvocation,
    derive_request_seeds,
    gather_pages,
    is_paged_cache,
    paged_extent,
    resolve_backend,
    spike_encode,
)
from repro.attention.ann_xla import sdpa as _sdpa, sdpa_chunked as _sdpa_chunked
from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig, YarnConfig
from repro.distributed.sharding import constrain
from repro.obs import trace_scope

# impls whose attention core consumes spike trains (LIF-encoded Q/K/V,
# rate-decoded output + out_norm rescale); "ann" is the only non-member
SPIKING_IMPLS = ("ssa", "spikformer", "sdsa", "qksum")
# spiking impls whose trains may live in the packed uint32 bit-plane cache
PACKED_IMPLS = ("ssa", "sdsa")

# ---------------------------------------------------------------------------
# initialisers
# ---------------------------------------------------------------------------


def draw_normal(key, shape) -> jax.Array:
    """``N(0, 1)`` f32 draw for a parameter initialiser.

    The barrier keeps XLA from folding the caller's scale into the sampler's
    own constants, so a jitted init draws exactly the values an eager one
    does; callers scale by numpy f32 constants for the same reason.
    """
    return jax.lax.optimization_barrier(jax.random.normal(key, shape))


def inv_sqrt(n: int) -> np.float32:
    """``1 / sqrt(n)`` in f32, folded on the host (never as an XLA rsqrt)."""
    return np.float32(1.0) / np.sqrt(np.float32(n))


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    return (draw_normal(key, (d_in, d_out)) * inv_sqrt(d_in)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_params(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def norm_apply(p: dict, x: jax.Array, kind: str, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and qwen2-vl M-RoPE)
# ---------------------------------------------------------------------------

MROPE_SECTIONS = (16, 24, 24)  # qwen2-vl (t, h, w) frequency-pair split


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_inv_freq(head_dim: int, theta: float, yarn: YarnConfig) -> np.ndarray:
    """YaRN frequencies (HF ``_compute_yarn_parameters``), float32.

    Pair ``i`` extrapolates (``theta^(-2i/hd)``) below the correction range
    ``[low, high]`` and interpolates (``/ factor``) above it, with a linear
    ramp between; ``low``/``high`` are the pairs that turn ``beta_fast`` /
    ``beta_slow`` times over ``original_max_position_embeddings`` positions,
    floored and ceiled.  Computed in float64 on the host."""
    dim = head_dim
    extrap = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    interp = extrap / yarn.factor

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (interp * ramp + extrap * (1.0 - ramp)).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Optional[YarnConfig] = None) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32.  ``yarn``: YaRN
    frequencies, with cos and sin scaled by its ``attention_factor``."""
    if yarn is None:
        freqs = _rope_freqs(x.shape[-1], theta)
    else:
        freqs = jnp.asarray(yarn_inv_freq(x.shape[-1], theta, yarn))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None:
        scale = np.float32(yarn.attention_factor)
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float) -> jax.Array:
    """qwen2-vl M-RoPE.  positions3: (3, B, S) (temporal, height, width ids).

    Frequency pairs are split into MROPE_SECTIONS; each section rotates with
    its own position stream.  hd must be 2*sum(sections) (=128 for qwen2-vl).
    """
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)  # (hd/2,)
    sec_ids = jnp.repeat(
        jnp.arange(3), jnp.array(MROPE_SECTIONS), total_repeat_length=hd // 2
    )  # (hd/2,) in {0,1,2}
    # pick the position stream per frequency pair
    pos = positions3.astype(jnp.float32)  # (3, B, S)
    pos_per_freq = pos[sec_ids]  # (hd/2, B, S)
    angles = jnp.moveaxis(pos_per_freq, 0, -1) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention orchestration: proj -> rope -> cache write -> backend dispatch
# (the ann/ssa/spikformer math lives in repro.attention backends; _sdpa /
# _sdpa_chunked re-exported above for callers of the ANN numerical core)
# ---------------------------------------------------------------------------


def padded_heads(a: AttentionConfig) -> int:
    return max(a.num_heads, a.pad_heads_to) if a.pad_heads_to else a.num_heads


def pad_q_weights(wq: jax.Array, wo: jax.Array, *, num_heads: int, kv: int,
                  hd: int, h_pad: int) -> tuple[jax.Array, jax.Array]:
    """Insert zero-weight query heads *per KV group* so GQA grouping (head i
    -> kv[i // groups]) is preserved exactly under padding."""
    g_old = num_heads // kv
    g_new = h_pad // kv
    d = wq.shape[0]
    wq4 = wq.reshape(d, kv, g_old, hd)
    wq4 = jnp.pad(wq4, ((0, 0), (0, 0), (0, g_new - g_old), (0, 0)))
    wo4 = wo.reshape(kv, g_old, hd, wo.shape[1])
    wo4 = jnp.pad(wo4, ((0, 0), (0, g_new - g_old), (0, 0), (0, 0)))
    return wq4.reshape(d, h_pad * hd), wo4.reshape(h_pad * hd, wo.shape[1])


def attention_params(key, cfg: ModelConfig, cross: bool = False) -> dict:
    a = cfg.attention
    d = cfg.d_model
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    h_pad = padded_heads(a)
    wq = dense_init(ks[0], d, a.num_heads * a.head_dim, dtype)
    wo = dense_init(ks[3], a.num_heads * a.head_dim, d, dtype)
    if h_pad != a.num_heads:
        # zero-weight padding heads: exact same function (their wo rows are
        # zero so they contribute nothing), TP-divisible head axis
        wq, wo = pad_q_weights(
            wq, wo, num_heads=a.num_heads, kv=a.num_kv_heads,
            hd=a.head_dim, h_pad=h_pad,
        )
    p = {
        "wq": wq,
        "wk": dense_init(ks[1], d, a.num_kv_heads * a.head_dim, dtype),
        "wv": dense_init(ks[2], d, a.num_kv_heads * a.head_dim, dtype),
        "wo": wo,
    }
    if a.impl in SPIKING_IMPLS:
        # post-attention rescale (spike rates live in [0,1])
        p["out_norm"] = norm_params(h_pad * a.head_dim, "rmsnorm")
    return p


def _cache_write(
    cache: dict,
    updates: dict,
    *,
    cache_index,
    layer_window: Optional[int],
    batch: int,
) -> dict:
    """Write per-token ``updates`` ({leaf: (B, s, ...) array}) into a KV
    cache whose leaves all carry the sequence axis at position 1 — shared by
    the dense ({"k","v","pos"}) and packed ({"ks","vs","pos"}) layouts.

    decode (``cache_index`` given): scalar index = one shared write offset
    (lock-step decode), (B,)-shaped = per-slot offsets (continuous-batching
    engine); rolling-window caches wrap the offset.  Updates wider than one
    token (``s > 1``) are the **prefix-extend** path (chunked prefill /
    future multi-token decode): token ``j`` of the chunk lands at offset
    ``cache_index + j`` (rolled per window layer), and tokens whose update
    position is ``-1`` (pad rows of a bucketed chunk) are dropped so page /
    cache rows beyond the real tokens keep their pristine fill.  prefill
    (``cache_index is None``): fill [0:s], keeping the tail when the update
    overflows the cache; a window layer's tail is rolled so position ``p``
    sits at row ``p % S_cache``, where decode's rolled writes expect it.

    Paged caches (leaves ``(num_pages, page_size, ...)`` plus a block table
    ``bt: (B, W)``) support the decode/prefix-extend paths only: each
    logical write offset (rolled for window layers, exactly as the slab
    layout rolls) is routed through the block table to a ``(page, row)``
    pair.  Inactive engine rows carry all-scratch tables, so their garbage
    writes land on the scratch page and never touch real pages or the
    pristine zero page.
    """
    if is_paged_cache(cache):
        if cache_index is None:
            raise ValueError(
                "paged KV caches are decode-only; the serving engine "
                "prefills through pages in chunks (prefix-extend) or "
                "scatters a slab row cache into them"
            )
        from repro.attention import PAGE_SCRATCH, PAGE_ZERO

        page_size = cache["pos"].shape[-1]
        bt = cache["bt"]
        extent = paged_extent(cache, layer_window)
        write = jnp.broadcast_to(
            jnp.asarray(cache_index, jnp.int32), (batch,)
        )
        s_upd = updates["pos"].shape[1]
        if s_upd == 1:
            r = write % extent if layer_window is not None else write
            # stale offsets on inactive rows may exceed the table span;
            # their entries are all scratch, so any clamped column is
            # equivalent
            col = jnp.clip(r // page_size, 0, bt.shape[1] - 1)
            page = jnp.take_along_axis(bt, col[:, None], axis=1)[:, 0]
            # the zero page is the immutable init fill every gather of
            # unallocated columns depends on; a write can only resolve to
            # it through zero-padded table entries (e.g. a replay tick for
            # a row whose next page is granted later that tick), and such
            # writes are re-issued after allocation — sink them to scratch
            # instead
            page = jnp.where(page == PAGE_ZERO, PAGE_SCRATCH, page)
            off = r % page_size
            new = {"bt": bt}
            for name, upd in updates.items():
                leaf = cache[name]
                new[name] = leaf.at[page, off].set(upd[:, 0].astype(leaf.dtype))
            return new
        # prefix-extend: chunk token j writes offset cache_index + j
        offs = write[:, None] + jnp.arange(s_upd, dtype=jnp.int32)[None, :]
        r = offs % extent if layer_window is not None else offs
        col = jnp.clip(r // page_size, 0, bt.shape[1] - 1)
        page = jnp.take_along_axis(bt, col, axis=1)          # (B, s)
        page = jnp.where(page == PAGE_ZERO, PAGE_SCRATCH, page)
        # bucketed chunks pad with position -1: sink those writes to the
        # scratch page so real page rows beyond the chunk stay pristine
        page = jnp.where(updates["pos"] < 0, PAGE_SCRATCH, page)
        off = r % page_size
        new = {"bt": bt}
        for name, upd in updates.items():
            leaf = cache[name]
            new[name] = leaf.at[page, off].set(upd.astype(leaf.dtype))
        return new

    s_cache = cache["pos"].shape[1]
    new = {}
    if cache_index is not None:
        write = cache_index % s_cache if layer_window is not None else cache_index
        per_row = jnp.ndim(write) == 1
        rows = jnp.arange(batch)
        s_upd = updates["pos"].shape[1]
        if s_upd > 1:
            # prefix-extend on a slab cache: per-token offsets, pad tokens
            # (position -1) dropped via an out-of-range scatter index
            offs = (
                jnp.broadcast_to(jnp.asarray(write, jnp.int32), (batch,))[:, None]
                + jnp.arange(s_upd, dtype=jnp.int32)[None, :]
            )
            if layer_window is not None:
                offs = offs % s_cache
            offs = jnp.where(updates["pos"] < 0, s_cache, offs)
            for name, upd in updates.items():
                leaf = cache[name]
                new[name] = leaf.at[rows[:, None], offs].set(
                    upd.astype(leaf.dtype), mode="drop"
                )
            return new
        for name, upd in updates.items():
            leaf = cache[name]
            if per_row:
                new[name] = leaf.at[rows, write].set(upd[:, 0].astype(leaf.dtype))
            else:
                start = (0, write) + (0,) * (leaf.ndim - 2)
                new[name] = jax.lax.dynamic_update_slice(
                    leaf, upd.astype(leaf.dtype), start
                )
    else:
        s_upd = updates["pos"].shape[1]
        for name, upd in updates.items():
            leaf = cache[name]
            if s_upd >= s_cache:
                upd = upd[:, -s_cache:]
                if layer_window is not None:
                    # rolled as decode writes it: position p at row
                    # p % S_cache, so the next write evicts the oldest row
                    upd = jnp.roll(upd, s_upd % s_cache, axis=1)
            new[name] = jax.lax.dynamic_update_slice(
                leaf, upd.astype(leaf.dtype), (0,) * leaf.ndim
            )
    return new


def attention_apply(
    p: dict,
    x: jax.Array,
    *,
    cfg: ModelConfig,
    layer_window: Optional[int],
    positions: jax.Array,
    rng: Optional[jax.Array] = None,
    seeds: Optional[jax.Array] = None,
    cache: Optional[dict] = None,
    cache_index: Optional[jax.Array] = None,
    kv_source: Optional[jax.Array] = None,
    causal: Optional[bool] = None,
) -> tuple[jax.Array, Optional[dict]]:
    """Full attention block: proj -> rope -> cache write -> backend -> out proj.

    Thin orchestration over the ``repro.attention`` backend registry: this
    function owns the projections, RoPE, KV-cache writes and spike encoding;
    the attention math itself (ann softmax / SSA eq. 5-6 / Spikformer) is a
    registered backend selected by ``AttentionConfig.impl``/``.backend`` and
    the call mode (train / prefill / decode).

    ``seeds``: (B,) uint32 per-sequence sampling seeds, already folded per
    layer (RNG contract v2) — the serving engine passes each request's own
    seed; callers that only have a PRNG key pass ``rng`` and per-row seeds
    are derived here (``derive_request_seeds``).  Backends also receive the
    absolute token positions for both queries and keys: SSA/Spikformer
    draws and masks key off positions, never off batch row or cache extent.

    cache: {"k","v": (B, S_cache, Hkv, hd), "pos": (B, S_cache)} for decode;
    cache_index: scalar write offset (decode step).  kv_source: cross-attn
    memory (whisper decoder).  Returns (out, updated_cache).
    """
    a = cfg.attention
    b, s, _ = x.shape
    if seeds is None:
        seeds = derive_request_seeds(rng, b)
    h_pad = padded_heads(a)
    causal = a.causal if causal is None else causal
    q = (x @ p["wq"]).reshape(b, s, h_pad, a.head_dim)
    kv_in = x if kv_source is None else kv_source
    s_kv = kv_in.shape[1]
    k = (kv_in @ p["wk"]).reshape(b, s_kv, a.num_kv_heads, a.head_dim)
    v = (kv_in @ p["wv"]).reshape(b, s_kv, a.num_kv_heads, a.head_dim)

    if a.rope_type == "rope":
        # per-kind RoPE: YaRN on global layers where the model states it
        yarn = a.global_yarn if layer_window is None else None
        q = apply_rope(q, positions, a.rope_theta, yarn)
        if kv_source is None:
            k = apply_rope(k, positions, a.rope_theta, yarn)
    elif a.rope_type == "mrope":
        q = apply_mrope(q, positions, a.rope_theta)
        if kv_source is None:
            k = apply_mrope(k, positions, a.rope_theta)

    # Serving TP shards heads here (training rules and bare calls resolve
    # these names to no-ops): heads are batch-like through the whole
    # attention core, so slicing them is pure data movement.
    q = constrain(q, "attn_heads")
    k = constrain(k, "attn_heads")
    v = constrain(v, "attn_heads")

    mode = (
        "train" if cache is None else ("decode" if cache_index is not None else "prefill")
    )
    spiking = a.impl in SPIKING_IMPLS
    new_cache = None
    kv_positions = None
    q_positions = None
    spike_k = spike_v = None       # (T, B, S_kv, H_kv, hd) pre-encoded trains
    packed_k = packed_v = None     # (B, S_kv, T, H_kv, W) uint32 bit-planes
    # M-RoPE carries (3, B, S) position ids; masking/caching uses the
    # temporal stream (index 0)
    pos_1d = positions[0] if positions.ndim == 3 else positions
    if cache is not None and "ks" in cache:
        # --- packed spiking KV cache (spike_storage="packed", ssa/sdsa) ---
        # Spike planes are packed along head_dim at kv-head granularity:
        # leaves (B, S_cache, T, H_kv, ceil(hd/32)) uint32.  New tokens are
        # LIF-encoded ONCE here and stored as bits; the dense path instead
        # re-encodes the full real-valued cache every decode step.
        from repro.bitpack import pack_spikes

        t_steps = a.ssa_time_steps
        # (T, B, s, H_kv, hd) spike trains -> packed (B, s, T, H_kv, W)
        ks_enc = spike_encode(k, t_steps)
        vs_enc = spike_encode(v, t_steps)
        new_cache = _cache_write(
            cache,
            {
                "ks": jnp.moveaxis(pack_spikes(ks_enc), 0, 2),
                "vs": jnp.moveaxis(pack_spikes(vs_enc), 0, 2),
                "pos": jnp.broadcast_to(pos_1d.astype(jnp.int32), (b, s)),
            },
            cache_index=cache_index,
            layer_window=layer_window,
            batch=b,
        )
        if cache_index is not None:
            # Decode attends over the cached spike planes.  They are handed
            # to the backend AS WORDS: ssa-fused-packed streams them into
            # the Pallas kernel (unpacked per-tile in VMEM only), while the
            # ssa-xla fallback unpacks them in XLA.  A paged cache is first
            # gathered back into the contiguous slab layout (bit-identical:
            # unallocated entries resolve to the pristine zero page, whose
            # pos = -1 masks them out — so any span covering the written
            # tokens decodes identically and the engine may bucket it).
            if is_paged_cache(new_cache):
                ext = paged_extent(new_cache, layer_window)
                packed_k = gather_pages(new_cache["ks"], new_cache["bt"], ext)
                packed_v = gather_pages(new_cache["vs"], new_cache["bt"], ext)
                kv_positions = gather_pages(
                    new_cache["pos"], new_cache["bt"], ext
                )
            else:
                packed_k, packed_v = new_cache["ks"], new_cache["vs"]
                kv_positions = new_cache["pos"]
            q_positions = jnp.broadcast_to(pos_1d.astype(jnp.int32), (b, s))
        else:
            # prefill attention reuses the trains encoded above (over ALL s
            # current tokens, pre-truncation) instead of re-encoding k_full —
            # encode-then-repeat == repeat-then-encode, so still bit-identical
            # to the dense path
            spike_k, spike_v = ks_enc, vs_enc
    elif cache is not None:
        # decode: append the new k/v at the rolling/linear write offset;
        # prefill: fill [0:s] (see _cache_write)
        new_cache = _cache_write(
            cache,
            {
                "k": k,
                "v": v,
                "pos": jnp.broadcast_to(pos_1d.astype(jnp.int32), (b, s)),
            },
            cache_index=cache_index,
            layer_window=layer_window,
            batch=b,
        )
        if cache_index is not None:
            if is_paged_cache(new_cache):
                ext = paged_extent(new_cache, layer_window)
                k = gather_pages(new_cache["k"], new_cache["bt"], ext)
                v = gather_pages(new_cache["v"], new_cache["bt"], ext)
                kv_positions = gather_pages(
                    new_cache["pos"], new_cache["bt"], ext
                )
            else:
                k, v = new_cache["k"], new_cache["v"]
                kv_positions = new_cache["pos"]
            q_positions = jnp.broadcast_to(pos_1d.astype(jnp.int32), (b, s))

    spike_q = None
    if spiking:
        t_steps = a.ssa_time_steps
        with trace_scope("repro/spike_encode"):
            spike_q = spike_encode(q, t_steps)
            if spike_k is None and packed_k is None:
                # dense-storage path: re-encode the real-valued K/V (for
                # decode, the whole cache) into trains at kv-head granularity
                spike_k = spike_encode(k, t_steps)
                spike_v = spike_encode(v, t_steps)
        if q_positions is None:
            # train/prefill: spiking draws and masks are keyed by absolute
            # positions (pad rows carry -1 and never draw), which is what
            # makes bucketed prefill and any cache extent sample the same
            # spikes for the real tokens (RNG contract v2)
            q_positions = jnp.broadcast_to(pos_1d.astype(jnp.int32), (b, s))
            if kv_source is None:
                kv_positions = jnp.broadcast_to(
                    pos_1d.astype(jnp.int32), (b, s_kv)
                )
            else:
                kv_positions = jnp.broadcast_to(
                    jnp.arange(s_kv, dtype=jnp.int32)[None], (b, s_kv)
                )

    backend = resolve_backend(a, mode)
    with trace_scope(f"repro/attn/{a.impl}/{mode}"):
        out = backend.apply(
            AttentionInvocation(
                a=a,
                mode=mode,
                q=q,
                k=k,
                v=v,
                groups=h_pad // a.num_kv_heads,
                causal=causal,
                window=layer_window,
                softcap=a.softcap,
                seeds=seeds,
                kv_positions=kv_positions,
                q_positions=q_positions,
                spike_q=spike_q,
                spike_k=spike_k,
                spike_v=spike_v,
                packed_k=packed_k,
                packed_v=packed_v,
            )
        )
    # Replicate before out_norm / the ``wo`` contraction: both reduce over
    # the (merged) head axis, and a cross-device float reduction there
    # could reorder sums and break the serving bit-identity contract.
    out = constrain(out, "attn_gather")
    out = out.astype(x.dtype).reshape(b, s, h_pad * a.head_dim)
    if a.impl in SPIKING_IMPLS:
        out = norm_apply(p["out_norm"], out, "rmsnorm", 1e-6)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLP (swiglu / geglu / gelu)
# ---------------------------------------------------------------------------


def mlp_params(key, d_model: int, d_ff: int, act: str, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {
            "wi": dense_init(ks[0], d_model, d_ff, dtype),
            "wg": dense_init(ks[1], d_model, d_ff, dtype),
            "wo": dense_init(ks[2], d_ff, d_model, dtype),
        }
    return {
        "wi": dense_init(ks[0], d_model, d_ff, dtype),
        "wo": dense_init(ks[2], d_ff, d_model, dtype),
    }


def mlp_apply(p: dict, x: jax.Array, act: str) -> jax.Array:
    if act == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
    if act == "geglu":
        return (jax.nn.gelu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]
    return jax.nn.gelu(x @ p["wi"]) @ p["wo"]


# ---------------------------------------------------------------------------
# MoE: shared + routed experts, top-k, dense one-hot dispatch
# ---------------------------------------------------------------------------


def moe_params(key, d_model: int, moe: MoEConfig, act: str, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    e, f = moe.num_experts, moe.expert_ffn_dim

    def ew(k, shape):
        return (draw_normal(k, shape) * inv_sqrt(d_model)).astype(dtype)

    p = {
        "router": dense_init(ks[0], d_model, e, jnp.float32),
        "wi": ew(ks[1], (e, d_model, f)),
        "wg": ew(ks[2], (e, d_model, f)),
        # divisor behind a barrier: XLA would turn x / c into x * (1 / c)
        "wo": (
            draw_normal(ks[3], (e, f, d_model))
            / jax.lax.optimization_barrier(np.sqrt(np.float32(f)))
        ).astype(dtype),
    }
    if moe.num_shared_experts:
        p["shared"] = mlp_params(ks[4], d_model, moe.shared_ffn_dim, act, dtype)
    return p


def _grouped_ffn(rows, wg, wi, wo, sizes, layer):
    """SwiGLU of expert-sorted rows through three ``moe_gmm`` calls.

    XLA cannot partition a Mosaic kernel, so under an active sharding mesh
    (``distributed.sharding.current_rules``: serving tensor parallelism)
    the three calls run inside ``shard_map``, whole on every shard: serving
    TP keeps the weights and the residual stream replicated, and splitting
    the ffn axis would need a float psum over ``wo``, which reorders sums.
    """
    from repro.attention import default_interpret
    from repro.distributed.sharding import current_rules
    from repro.kernels.moe_gmm import moe_gmm
    from jax.sharding import PartitionSpec as P

    def ffn(rows, wg, wi, wo, sizes, layer):
        kw = dict(layer=layer, interpret=default_interpret())
        g = moe_gmm(rows, wg, sizes, **kw)
        u = moe_gmm(rows, wi, sizes, **kw)
        return moe_gmm(jax.nn.silu(g) * u, wo, sizes, **kw)

    args = (rows, wg, wi, wo, sizes, layer)
    rules = current_rules()
    if rules is None:
        return ffn(*args)
    return jax.shard_map(ffn, mesh=rules.mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def moe_dropless(p: dict, x: jax.Array, moe: MoEConfig, valid: jax.Array,
                 layer=None) -> tuple[jax.Array, jax.Array]:
    """Dropless top-k experts over every token of the call (serving).

    Softmax router in float32 (``HIGHEST`` precision), top-k, the k
    weights renormalised to sum to 1.  Every (token, expert) pair of the
    call — all rows, all tokens of a chunk — is sorted by expert and run
    through the grouped matmul (``kernels.moe_gmm``), which visits only
    experts with rows; the k outputs of a token are combined in float32 in
    rank order.  A token's output depends on that token alone, so one-shot
    prefill, chunked prefill and decode compute the same rows, and no pair
    is ever dropped.  Tokens with ``valid`` False (pad positions) route
    nowhere and come out 0.

    ``layer``: where the expert matrices come stacked ``(L, E, ...)``
    (a scanned layer stack), the index of this layer (may be traced).

    Returns ``(out (B, S, d), stats)``: ``stats`` int32 ``(2,)`` = (routed
    pairs, experts with at least one row) of this call.
    """
    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    n = b * s
    xf = x.reshape(n, d)
    live = valid.reshape(n)
    with trace_scope("repro/moe_route"):
        logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        # pairs of dead tokens sort last, past every expert's rows
        expert = jnp.where(live[:, None], top_i, e).reshape(n * k)
        order = jnp.argsort(expert, stable=True)
        sizes = jnp.bincount(expert, length=e + 1)[:e].astype(jnp.int32)
        rows = xf[order // k]
    with trace_scope("repro/moe_experts"):
        y = _grouped_ffn(rows, p["wg"], p["wi"], p["wo"], sizes, layer)
        # back to (token, rank) order, then combine ranks 0..k-1 in turn
        back = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        y = y[back].reshape(n, k, d)
        out = top_p[:, 0:1] * y[:, 0].astype(jnp.float32)
        for r in range(1, k):
            out = out + top_p[:, r:r + 1] * y[:, r].astype(jnp.float32)
        out = jnp.where(live[:, None], out, 0.0).astype(x.dtype)
    stats = jnp.stack([live.sum() * k, (sizes > 0).sum()]).astype(jnp.int32)
    return out.reshape(b, s, d), stats


def moe_apply(p: dict, x: jax.Array, moe: MoEConfig, act: str,
              capacity_factor: float = 1.25, *,
              valid: Optional[jax.Array] = None, layer=None):
    """Top-k routed experts (+ shared experts).

    Serving (``valid`` given: (B, S) bool, False on pad tokens — every
    call with a KV cache passes it) is **dropless**: :func:`moe_dropless`
    computes each token's k experts whatever the batch, and the second
    return value is its int32 ``(2,)`` stats (routed pairs, experts with
    rows); ``layer`` indexes expert matrices that come stacked.  Training
    (``valid`` None) keeps the capacity dispatch below and returns the
    load-balancing aux loss.

    Capacity dispatch: *per-sequence-row* sort-based.
    Routing, capacity ranking and the scatter/gather all happen within each
    batch row (vmapped over B): under GSPMD the B axis is data-sharded, so
    the sort and scatters stay shard-local -- a global flat dispatch forces
    a replicated (N_tokens x d) buffer + collective sort (measured on the
    256-chip mesh: ~69 GB of all-reduce per layer).  Expert FFN weights
    shard over `model` on the ffn dim (Megatron col/row style), so the only
    per-layer collective is the psum of the (B, S, D) combine.  Capacity
    C = ceil(S*K*cf/E) per row; overflow drops (Switch-style).  Returns
    (out, aux_loss).
    """
    if valid is not None:
        out, stats = moe_dropless(p, x, moe, valid, layer)
        if moe.num_shared_experts:
            out = out + mlp_apply(p["shared"], x, act)
        return out, stats
    from repro.distributed.sharding import constrain as _constrain

    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    cap = max(1, int(-(-s * k * capacity_factor // e)))  # per-row capacity

    logits = x.astype(jnp.float32) @ p["router"]  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # (B, S, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    def dispatch_row(x_row, top_i_row):
        """x_row: (S, d); top_i_row: (S, K) -> (E*cap, d) buffer + indices."""
        flat_e = top_i_row.reshape(-1)                       # (S*K,)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        sorted_tok = order // k
        rank = jnp.arange(s * k) - jnp.searchsorted(sorted_e, jnp.arange(e))[sorted_e]
        slot = jnp.where(rank < cap, sorted_e * cap + rank, e * cap)
        buf = jnp.zeros((e * cap, d), x_row.dtype).at[slot].set(
            x_row[sorted_tok], mode="drop"
        )
        return buf, (order, sorted_tok, rank, slot)

    def expert_ffn_and_combine(x_blk, top_i_blk, top_p_blk, wg_blk, wi_blk, wo_blk):
        """dispatch -> expert FFN -> combine.  Runs either globally (GSPMD)
        or as the per-shard body of a shard_map island (explicit psum)."""
        bufs, (order, sorted_tok, rank, slot) = jax.vmap(dispatch_row)(
            x_blk, top_i_blk
        )
        bb = x_blk.shape[0]
        he = bufs.reshape(bb, e, cap, d)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", he, wg_blk))
        h = h * jnp.einsum("becd,edf->becf", he, wi_blk)
        ye = jnp.einsum("becf,efd->becd", h, wo_blk).reshape(bb, e * cap, d)

        def combine_row(ye_row, order_row, tok_row, rank_row, slot_row, gates_row):
            gathered = ye_row.at[slot_row].get(mode="fill", fill_value=0)
            gates_sorted = gates_row.reshape(-1)[order_row].astype(ye_row.dtype)
            contrib = jnp.where(
                (rank_row < cap)[:, None], gathered * gates_sorted[:, None], 0.0
            )
            return jnp.zeros((s, d), ye_row.dtype).at[tok_row].add(contrib)

        return jax.vmap(combine_row)(ye, order, sorted_tok, rank, slot, top_p_blk)

    from repro.distributed.sharding import current_rules

    rules = current_rules()
    f_dim = p["wg"].shape[-1]
    if (
        rules is not None
        and rules.model > 1
        and f_dim % rules.model == 0
        and rules.batch_shardable
        and b % rules.data_size == 0
    ):
        # shard_map island: the combine is LINEAR in the expert output, so it
        # commutes with the f-contraction psum — doing combine BEFORE psum
        # reduces the per-layer collective from (B, E*C, d) slot-level f32
        # all-reduces to one (B, S, d) psum (measured ~6x fewer bytes).
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        dspec = rules.data

        def island(x_l, ti_l, tp_l, wg_l, wi_l, wo_l):
            out_partial = expert_ffn_and_combine(x_l, ti_l, tp_l, wg_l, wi_l, wo_l)
            return jax.lax.psum(out_partial, "model")

        out = shard_map(
            island,
            mesh=rules.mesh,
            in_specs=(
                P(dspec, None, None),       # x
                P(dspec, None, None),       # top_i
                P(dspec, None, None),       # top_p
                P(None, None, "model"),     # wg (E, d, f)
                P(None, None, "model"),     # wi
                P(None, "model", None),     # wo (E, f, d)
            ),
            out_specs=P(dspec, None, None),
        )(x, top_i, top_p.astype(x.dtype), p["wg"], p["wi"], p["wo"])
    else:
        out = expert_ffn_and_combine(x, top_i, top_p, p["wg"], p["wi"], p["wo"])
    out = _constrain(out, "btd")

    if moe.num_shared_experts:
        out = out + mlp_apply(p["shared"], x, act)
    # load-balancing aux loss (Switch-style)
    frac_tokens = jnp.mean(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=(0, 1, 2))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs) * moe.router_aux_coef
    return out, aux
