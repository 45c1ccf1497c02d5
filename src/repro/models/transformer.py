"""Unified decoder-only transformer LM (dense / MoE / VLM families).

Layers are grouped into *pattern slots* (gemma2's "LG" local/global
alternation => 2 slots) and scanned: params carry a leading ``L/num_slots``
stack axis, so HLO size is O(1) in depth and 512-device dry-run compiles stay
fast.  KV caches are stacked the same way and threaded through the scan.

The attention implementation (`ann` | `ssa` | `spikformer`) is a config
switch — the paper's technique is a first-class feature of every arch here.
Which *kernel* realises it (XLA reference vs fused Pallas, dense vs packed
KV decode) is a second, orthogonal switch: `AttentionConfig.backend`
dispatches through the `repro.attention` registry per call mode, and the
request-addressed counter-RNG seed derivation makes all SSA backends
bit-identical for the same per-sequence seeds — independent of batch row,
pad bucket and cache extent (see docs/attention_backends.md).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.attention import derive_request_seeds, fold_layer_seeds, window_ring
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import constrain
from repro.obs import trace_scope
from .blocks import (
    PACKED_IMPLS,
    attention_apply,
    attention_params,
    draw_normal,
    mlp_apply,
    mlp_params,
    moe_apply,
    moe_params,
    norm_apply,
    norm_params,
)


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array]):
    """Vocab-sharding-friendly CE: one-hot contraction (reduces over the
    sharded vocab axis as a psum) + f32 logsumexp; no full-vocab gather."""
    logits = constrain(logits, "btv")
    l32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(l32, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logits.dtype)
    ll = jnp.sum(l32 * onehot.astype(jnp.float32), axis=-1)
    nll = lse - ll
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


@functools.lru_cache(maxsize=None)
def _jitted_init(cfg: ModelConfig):
    """One compiled init per config, shared by every model built from it."""
    return jax.jit(DecoderLM(cfg)._init)


class DecoderLM:
    """Families: dense, moe, vlm.  VLM/audio frontends are stubbed: the model
    accepts precomputed embeddings via ``batch["embeds"]``."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.pattern = list(cfg.attention.layer_pattern)
        assert cfg.num_layers % len(self.pattern) == 0
        self.steps = cfg.num_layers // len(self.pattern)
        # gemma-style sqrt(d) embedding scale
        self.embed_scale = (
            float(jnp.sqrt(jnp.float32(cfg.d_model))) if cfg.post_norms else 1.0
        )

    # ------------------------------------------------------------------
    def _slot_window(self, slot: int) -> Optional[int]:
        return (
            self.cfg.attention.sliding_window
            if self.pattern[slot] == "L"
            else None
        )

    def _layer_params(self, key) -> dict:
        cfg = self.cfg
        ka, kf = jax.random.split(key)
        p = {"ln_attn": norm_params(cfg.d_model, cfg.norm), "attn": attention_params(ka, cfg)}
        p["ln_mlp"] = norm_params(cfg.d_model, cfg.norm)
        if cfg.moe:
            p["moe"] = moe_params(kf, cfg.d_model, cfg.moe, cfg.act, jnp.dtype(cfg.dtype))
        else:
            p["mlp"] = mlp_params(kf, cfg.d_model, cfg.d_ff, cfg.act, jnp.dtype(cfg.dtype))
        if cfg.post_norms:
            p["ln_attn_post"] = norm_params(cfg.d_model, cfg.norm)
            p["ln_mlp_post"] = norm_params(cfg.d_model, cfg.norm)
        return p

    def init(self, key) -> dict:
        """Random parameters, built as one jitted program.

        Layers are drawn one at a time (``lax.map``) and each f32 draw is
        cast to ``cfg.dtype`` inside the program, so the only f32
        temporaries are one layer's (or the embedding's) worth — a
        full-width stack fits one chip.  The draws are bit-identical to
        eager, vmapped initialisation (``blocks.draw_normal``).
        """
        return _jitted_init(self.cfg)(key)

    def _init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        params: dict[str, Any] = {
            "embed": (
                draw_normal(k_embed, (cfg.vocab_size, cfg.d_model))
                * np.float32(0.02)
            ).astype(dtype),
            "final_norm": norm_params(cfg.d_model, cfg.norm),
        }
        # stacked per pattern slot
        slots = []
        for s in range(len(self.pattern)):
            keys = jax.random.split(jax.random.fold_in(k_layers, s), self.steps)
            slots.append(jax.lax.map(self._layer_params, keys))
        params["slots"] = slots
        if not cfg.tie_embeddings:
            params["lm_head"] = (
                draw_normal(k_head, (cfg.d_model, cfg.vocab_size))
                * np.float32(0.02)
            ).astype(dtype)
        return params

    # ------------------------------------------------------------------
    def _block(self, p, x, *, slot, positions, seeds, cache, cache_index,
               step=None):
        """One layer.  ``step``: with serving experts, this layer's index in
        its slot's stack, whose expert matrices ``p["moe"]`` holds whole."""
        cfg = self.cfg
        h = norm_apply(p["ln_attn"], x, cfg.norm, cfg.norm_eps)
        attn_out, new_cache = attention_apply(
            p["attn"],
            h,
            cfg=cfg,
            layer_window=self._slot_window(slot),
            positions=positions,
            seeds=seeds,
            cache=cache,
            cache_index=cache_index,
        )
        if cfg.post_norms:
            attn_out = norm_apply(p["ln_attn_post"], attn_out, cfg.norm, cfg.norm_eps)
        x = x + attn_out
        x = constrain(x, "btd_sp")
        h = norm_apply(p["ln_mlp"], x, cfg.norm, cfg.norm_eps)
        aux = 0.0
        if cfg.moe and cache is not None:
            # serving: dropless, ``aux`` is the layer's int32 (2,) stats
            pos = positions[0] if positions.ndim == 3 else positions
            ff, aux = moe_apply(p["moe"], h, cfg.moe, cfg.act, valid=pos >= 0,
                                layer=step)
        elif cfg.moe:
            ff, aux = moe_apply(p["moe"], h, cfg.moe, cfg.act)
        else:
            ff = mlp_apply(p["mlp"], h, cfg.act)
        if cfg.post_norms:
            ff = norm_apply(p["ln_mlp_post"], ff, cfg.norm, cfg.norm_eps)
        x = x + ff
        return constrain(x, "btd_sp"), new_cache, aux

    def forward(
        self,
        params: dict,
        batch: dict,
        *,
        cache: Optional[list] = None,
        cache_index: Optional[jax.Array] = None,
        rng: Optional[jax.Array] = None,
        seeds: Optional[jax.Array] = None,
        remat: str = "none",
    ):
        """Returns (hidden (B,S,D), new_cache, aux).

        ``aux``: the load-balancing loss without a cache; with one, the
        expert layers' int32 ``(num_layers, 2)`` stats in layer order
        (routed pairs, experts with rows; ``moe_apply``), ``None`` for a
        model without experts.

        ``seeds``: (B,) uint32 per-sequence SSA sampling seeds (RNG contract
        v2) — the serving engine passes each request's own seed so a
        sequence samples identically in any batch row/width.  When absent
        they derive from ``rng`` (``derive_request_seeds``; training gets
        fresh independent per-row streams per step).  Layer identity is
        folded in here via a flat layer counter carried through the scan —
        a pure function of (seed, layer), identical between prefill and
        decode, which is what the serving cache-identity contract rests on.
        """
        cfg = self.cfg
        if "embeds" in batch:
            x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = x * jnp.asarray(self.embed_scale, x.dtype)
        x = constrain(x, "btd_sp")
        positions = batch["positions"]
        if seeds is None:
            seeds = derive_request_seeds(rng, x.shape[0])
        seeds = jnp.asarray(seeds, jnp.uint32)

        nslots = len(self.pattern)

        serving = cache is not None
        moe_stats = serving and cfg.moe is not None
        slot_stack = params["slots"]
        experts = None
        if moe_stats:
            # the grouped-matmul kernel takes each slot's stacked expert
            # matrices whole and indexes the layer itself: a per-step slice
            # would copy every expert of the layer into a temporary (a
            # custom call's operand is a whole buffer)
            experts = [{n: sp["moe"][n] for n in ("wi", "wg", "wo")}
                       for sp in slot_stack]
            slot_stack = [
                {**sp, "moe": {n: w for n, w in sp["moe"].items()
                               if n not in ("wi", "wg", "wo")}}
                for sp in slot_stack]

        def body(carry, xs):
            x, li, aux_acc = carry
            slot_params, slot_caches = xs
            new_caches, stats = [], []
            step = (li // jnp.uint32(nslots)).astype(jnp.int32)
            for s in range(nslots):
                c = slot_caches[s] if slot_caches is not None else None
                p = slot_params[s]
                if experts is not None:
                    p = {**p, "moe": {**p["moe"], **experts[s]}}
                x, nc, aux = self._block(
                    p,
                    x,
                    slot=s,
                    positions=positions,
                    seeds=fold_layer_seeds(seeds, li),
                    cache=c,
                    cache_index=cache_index,
                    step=step if experts is not None else None,
                )
                li = li + jnp.uint32(1)
                new_caches.append(nc)
                if moe_stats:
                    stats.append(aux)
                elif not serving:
                    aux_acc = aux_acc + aux
            if slot_caches is None:
                new_caches = None
            ys = (new_caches, jnp.stack(stats) if moe_stats else None)
            return (x, li, aux_acc), ys

        if remat != "none":
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if remat == "dots"
                else jax.checkpoint_policies.nothing_saveable
            )
            body = jax.checkpoint(body, policy=policy)

        li0 = jnp.uint32(0)
        xs = (slot_stack, cache)
        if cfg.scan_layers:
            (x, _, aux_total), (new_cache, stats) = jax.lax.scan(
                body, (x, li0, 0.0), xs)
        else:
            # unrolled (depth-calibration mode): same body, python loop
            carry = (x, li0, 0.0)
            outs = []
            for i in range(self.steps):
                xs_i = jax.tree.map(lambda a: a[i], xs)
                carry, ys = body(carry, xs_i)
                outs.append(ys)
            (x, _, aux_total) = carry
            new_cache, stats = (
                jax.tree.map(lambda *a: jnp.stack(a), *outs)
                if serving
                else (None, None)
            )
        x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        if serving:
            # (steps, slots, 2) -> flat layer order
            aux_total = None if stats is None else stats.reshape(-1, 2)
        return x, new_cache, aux_total

    def logits(self, params, hidden):
        cfg = self.cfg
        if cfg.tie_embeddings:
            out = hidden @ params["embed"].T.astype(hidden.dtype)
        else:
            out = hidden @ params["lm_head"]
        if cfg.final_softcap is not None:
            out = (jnp.tanh(out.astype(jnp.float32) / cfg.final_softcap)
                   * cfg.final_softcap).astype(out.dtype)
        return constrain(out, "btv")

    # ------------------------------------------------------------------
    def loss(self, params, batch, rng=None, remat: str = "none"):
        hidden, _, aux = self.forward(params, batch, rng=rng, remat=remat)
        logits = self.logits(params, hidden)
        return cross_entropy(logits, batch["labels"], batch.get("mask")) + aux

    def prefill(self, params, batch, cache, rng=None, logits_at=None,
                seeds=None):
        """Prefill the cache; returns (next-token logits, cache).

        ``logits_at``: position (scalar, may be traced) whose logits to
        return instead of the last row — the serving engine's bucketed
        prefill pads prompts to a power of two and reads the logits of the
        real last token, so one compiled prefill serves a whole bucket.
        ``seeds``: per-sequence sampling seeds (see :meth:`forward`).
        """
        with trace_scope("repro/prefill"):
            hidden, new_cache, _ = self.forward(
                params, batch, cache=cache, rng=rng, seeds=seeds
            )
        if logits_at is None:
            last = hidden[:, -1:]
        else:
            last = jax.lax.dynamic_slice_in_dim(hidden, logits_at, 1, axis=1)
        return self.logits(params, last), new_cache

    def decode_step(self, params, batch, cache, cache_index, rng=None,
                    seeds=None, logits_at=None, with_stats=False):
        """Advance the cache by the batch's tokens; returns (logits, cache),
        and with ``with_stats`` also the expert layers' int32
        ``(num_layers, 2)`` stats (see :meth:`forward`; ``None`` without
        experts).

        With one token per row this is the classic decode tick.  Wider
        batches are the **prefix-extend** path (chunked prefill): token
        ``j`` of each row writes cache offset ``cache_index + j`` and
        attends over the previously-written cache plus the chunk itself —
        causality falls out of the absolute positions every backend masks
        by.  ``logits_at`` (scalar, may be traced) selects a single
        sequence index whose logits to return (the chunked-prefill engine
        reads the last *real* token of a padded chunk); default: logits
        for every position — the speculative-decode verifier: position
        ``j``'s logits score the token at absolute position
        ``cache_index + j + 1``, bit-identical to decoding one token at a
        time because RNG contract v2 keys draws by absolute position,
        never chunk shape (``tests/test_speculative.py``).
        """
        with trace_scope("repro/decode_step"):
            hidden, new_cache, stats = self.forward(
                params, batch, cache=cache, cache_index=cache_index, rng=rng,
                seeds=seeds,
            )
        if logits_at is not None:
            hidden = jax.lax.dynamic_slice_in_dim(hidden, logits_at, 1, axis=1)
        if with_stats:
            return self.logits(params, hidden), new_cache, stats
        return self.logits(params, hidden), new_cache

    # ------------------------------------------------------------------
    # beyond-paper: SSA-linear (expectation-mode) O(1)-state decode.
    # E[SSA] = Q (K^T V) / (N D_K) is associative, so dense archs can run
    # long-context decode with a (D_K x D_K) running state per head instead
    # of a seq-length KV cache (DESIGN.md §5; core/linear_decode.py).
    # ------------------------------------------------------------------
    def linear_decode_step(self, params, batch, state, rng=None):
        """state: list per slot of {"m": (L, B, H, dk, dk), "count": (L, B, H)}."""
        from repro.core.linear_decode import LinearSSAState
        from repro.models.blocks import apply_rope, padded_heads

        cfg = self.cfg
        a = cfg.attention
        h_pad = padded_heads(a)
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = x * jnp.asarray(self.embed_scale, x.dtype)
        positions = batch["positions"]
        nslots = len(self.pattern)

        def body(carry, xs):
            x, = carry
            slot_params, slot_states = xs
            new_states = []
            for s_idx in range(nslots):
                p = slot_params[s_idx]
                st = slot_states[s_idx]
                from .blocks import mlp_apply, moe_apply, norm_apply

                h = norm_apply(p["ln_attn"], x, cfg.norm, cfg.norm_eps)
                b, s, _ = h.shape
                q = (h @ p["attn"]["wq"]).reshape(b, s, h_pad, a.head_dim)
                k = (h @ p["attn"]["wk"]).reshape(b, s, a.num_kv_heads, a.head_dim)
                v = (h @ p["attn"]["wv"]).reshape(b, s, a.num_kv_heads, a.head_dim)
                if a.rope_type == "rope":
                    yarn = (a.global_yarn if self._slot_window(s_idx) is None
                            else None)
                    q = apply_rope(q, positions, a.rope_theta, yarn)
                    k = apply_rope(k, positions, a.rope_theta, yarn)
                # rate coding in expectation: sigmoid-normalised projections
                q_r = jax.nn.sigmoid(q.astype(jnp.float32))[:, 0]  # (B, H, dk)
                k_r = jax.nn.sigmoid(k.astype(jnp.float32))[:, 0]
                v_r = jax.nn.sigmoid(v.astype(jnp.float32))[:, 0]
                groups = h_pad // a.num_kv_heads
                k_r = jnp.repeat(k_r, groups, axis=1)
                v_r = jnp.repeat(v_r, groups, axis=1)
                # state update: m += k v^T ; count += 1   (eq. 5/6 in E[.])
                m_new = st["m"] + k_r[..., :, None] * v_r[..., None, :]
                c_new = st["count"] + 1.0
                num = jnp.einsum("bhd,bhde->bhe", q_r, m_new)
                rate = num / (jnp.maximum(c_new, 1.0)[..., None] * a.head_dim)
                out = rate[:, None].transpose(0, 1, 2, 3)  # (B, 1, H, dk)
                out = out.reshape(b, s, h_pad * a.head_dim).astype(x.dtype)
                if "out_norm" in p["attn"]:
                    out = norm_apply(p["attn"]["out_norm"], out, "rmsnorm", 1e-6)
                x = x + out @ p["attn"]["wo"]
                h2 = norm_apply(p["ln_mlp"], x, cfg.norm, cfg.norm_eps)
                if cfg.moe:
                    ff, _ = moe_apply(p["moe"], h2, cfg.moe, cfg.act,
                                      valid=positions >= 0)
                else:
                    ff = mlp_apply(p["mlp"], h2, cfg.act)
                x = x + ff
                new_states.append({"m": m_new, "count": c_new})
            return (x,), new_states

        (x,), new_state = jax.lax.scan(body, (x,), (params["slots"], state))
        from .blocks import norm_apply

        x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        return self.logits(params, x), new_state

    def linear_state_specs(self, shape: ShapeConfig) -> list:
        from repro.models.blocks import padded_heads

        a = self.cfg.attention
        b = shape.global_batch
        h = padded_heads(a)
        return [
            {
                "m": jax.ShapeDtypeStruct(
                    (self.steps, b, h, a.head_dim, a.head_dim), jnp.float32
                ),
                "count": jax.ShapeDtypeStruct((self.steps, b, h), jnp.float32),
            }
            for _ in range(len(self.pattern))
        ]

    # ------------------------------------------------------------------
    # dry-run specs
    # ------------------------------------------------------------------
    def _positions_spec(self, b, s):
        if self.cfg.attention.rope_type == "mrope":
            return jax.ShapeDtypeStruct((3, b, s), jnp.int32)
        return jax.ShapeDtypeStruct((b, s), jnp.int32)

    def input_specs(self, shape: ShapeConfig) -> dict:
        b = shape.global_batch
        cfg = self.cfg
        if shape.kind == "train":
            s = shape.seq_len
            base = {
                "positions": self._positions_spec(b, s),
                "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
            }
        elif shape.kind == "prefill":
            s = shape.seq_len
            base = {"positions": self._positions_spec(b, s)}
        else:  # decode: one new token against a seq_len cache
            s = 1
            base = {"positions": self._positions_spec(b, 1)}
        if cfg.frontend == "embeddings":
            base["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.dtype(cfg.dtype))
        else:
            base["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        return base

    def cache_specs(self, shape: ShapeConfig,
                    page_size: Optional[int] = None) -> list:
        """Stacked KV-cache ShapeDtypeStructs per pattern slot.

        Window slots hold ``min(window, S)`` rows, or, with ``page_size``
        (a paged engine's staging row), the paged layout's ring
        (``attention.window_ring``), so a staged row scatters into pages
        row for row.
        ``spike_storage="packed"`` (SSA impl) swaps the real-valued k/v
        leaves for uint32 spike bit-planes — (steps, B, S, T, H_kv,
        ceil(hd/32)) — 1 bit per cached spike instead of a 16/32-bit lane
        (see repro.bitpack / docs/bitpack.md)."""
        cfg = self.cfg
        a = cfg.attention
        b = shape.global_batch
        dtype = jnp.dtype(cfg.dtype)
        packed = a.impl in PACKED_IMPLS and a.spike_storage == "packed"
        if packed:
            from repro.bitpack import packed_width

            words = packed_width(a.head_dim)
        slots = []
        for s_idx in range(len(self.pattern)):
            w = self._slot_window(s_idx)
            if w is not None and page_size:
                w = window_ring(w, page_size)
            s_cache = min(w, shape.seq_len) if w is not None else shape.seq_len
            if packed:
                plane = jax.ShapeDtypeStruct(
                    (self.steps, b, s_cache, a.ssa_time_steps, a.num_kv_heads,
                     words),
                    jnp.uint32,
                )
                slots.append(
                    {
                        "ks": plane,
                        "vs": plane,
                        "pos": jax.ShapeDtypeStruct(
                            (self.steps, b, s_cache), jnp.int32
                        ),
                    }
                )
                continue
            slots.append(
                {
                    "k": jax.ShapeDtypeStruct(
                        (self.steps, b, s_cache, a.num_kv_heads, a.head_dim), dtype
                    ),
                    "v": jax.ShapeDtypeStruct(
                        (self.steps, b, s_cache, a.num_kv_heads, a.head_dim), dtype
                    ),
                    "pos": jax.ShapeDtypeStruct((self.steps, b, s_cache), jnp.int32),
                }
            )
        return slots

    def init_cache(
        self,
        batch: int,
        seq: int,
        *,
        layout: str = "slab",
        num_pages: Optional[int] = None,
        page_size: Optional[int] = None,
    ) -> list:
        """Fresh decode cache.

        ``layout="slab"`` (default): one contiguous ``(B, S_cache, ...)``
        region per batch row, per-layer extents clamped to sliding windows
        (to the paged ring where ``page_size`` is given; see
        :meth:`cache_specs`).

        ``layout="paged"``: a shared page pool — every leaf becomes
        ``(steps, num_pages, page_size, ...)`` plus a block table ``bt:
        (steps, batch, ceil(seq/page_size))`` of page ids per decode row
        (broadcast over the scanned layer axis).  Page ``PAGE_ZERO`` holds
        the init fill and is never written (the whole pool starts as init
        fill); tables start all-``PAGE_SCRATCH`` (every row inactive).  The
        serving engine owns allocation; ``models.blocks`` writes and
        gathers through the table (see ``repro.attention.gather_pages``).
        Note the engine's choice of ``cache_layout`` lives in
        ``AttentionConfig``; this method always needs the explicit request
        so reference decode loops can keep building slab caches.
        """
        shape = ShapeConfig("tmp", seq, batch, "decode")
        a = self.cfg.attention
        fill_u32 = None
        if a.impl in PACKED_IMPLS and a.spike_storage == "packed":
            # Empty packed slots must hold the spike pattern the LIF encoder
            # emits for zero input (enc(0) fires — softplus(0) > 0 drives the
            # membrane), because the dense path re-encodes its zero-filled
            # real cache every step.  Packing enc(0) keeps the two storage
            # modes bit-identical even over never-written slots.
            from repro.bitpack import pack_spikes
            from .blocks import spike_encode

            zero = jnp.zeros((1, 1, a.num_kv_heads, a.head_dim), jnp.float32)
            zp = pack_spikes(spike_encode(zero, a.ssa_time_steps))
            # (T, 1, 1, H_kv, W) -> (1, 1, T, H_kv, W), broadcast per leaf
            fill_u32 = jnp.moveaxis(zp, 0, 2)

        def init_leaf(s):
            if s.dtype == jnp.int32:
                return jnp.full(s.shape, -1, s.dtype)
            if s.dtype == jnp.uint32 and fill_u32 is not None:
                return jnp.broadcast_to(fill_u32[None], s.shape)
            return jnp.zeros(s.shape, s.dtype)

        if layout == "slab":
            return jax.tree.map(init_leaf, self.cache_specs(shape, page_size))
        if layout != "paged":
            raise ValueError(f"cache layout must be 'slab' or 'paged', got {layout!r}")
        if num_pages is None or page_size is None:
            raise ValueError("layout='paged' requires num_pages and page_size")

        from repro.attention import NUM_RESERVED_PAGES, PAGE_SCRATCH

        if num_pages <= NUM_RESERVED_PAGES:
            raise ValueError(
                f"num_pages={num_pages} leaves no allocatable pages "
                f"({NUM_RESERVED_PAGES} ids are reserved)"
            )
        packed = a.impl in PACKED_IMPLS and a.spike_storage == "packed"
        if packed:
            from repro.bitpack import packed_width

            words = packed_width(a.head_dim)
        width = -(-seq // page_size)
        slots = []
        for _ in range(len(self.pattern)):
            if packed:
                plane = jax.ShapeDtypeStruct(
                    (self.steps, num_pages, page_size, a.ssa_time_steps,
                     a.num_kv_heads, words),
                    jnp.uint32,
                )
                d = {"ks": plane, "vs": plane}
            else:
                kv = jax.ShapeDtypeStruct(
                    (self.steps, num_pages, page_size, a.num_kv_heads,
                     a.head_dim),
                    jnp.dtype(self.cfg.dtype),
                )
                d = {"k": kv, "v": kv}
            d["pos"] = jax.ShapeDtypeStruct(
                (self.steps, num_pages, page_size), jnp.int32
            )
            leaf_d = {name: init_leaf(spec) for name, spec in d.items()}
            leaf_d["bt"] = jnp.full(
                (self.steps, batch, width), PAGE_SCRATCH, jnp.int32
            )
            slots.append(leaf_d)
        return slots
