"""Expert LMs with window and full layers (mellum2): a dropless top-k expert
layer, window layers on a paged ring, chunked prefill into pages, decode
through the packed paged cache.

Counts are per token and window-aware: the active weights are attention,
the router and the top-k experts' three matrices (never all of them), and
a window layer's query sees at most ``sliding_window`` keys.
"""
from __future__ import annotations

import numpy as np

from ..reference.moe_lm import Reference  # noqa: F401
from .decoder_lm import (  # noqa: F401  (same engine settings and shapes)
    head_params,
    init_params,
    max_total,
    reference_kwargs,
    vocab,
    warmup_prompts,
)

WINDOW = "sliding_attention"


def _rope_parameters(cfg) -> dict:
    a = cfg.attention
    y = a.global_yarn
    full = {"rope_type": "default", "rope_theta": a.rope_theta}
    if y is not None:
        full = {"rope_type": "yarn", "rope_theta": a.rope_theta, "factor": y.factor,
                "original_max_position_embeddings": y.original_max_position_embeddings,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "attention_factor": y.attention_factor}
    return {"full_attention": full,
            "sliding_attention": {"rope_type": "default", "rope_theta": a.rope_theta}}


def stated_sizes(file: dict, cfg, model) -> dict:
    a, moe = cfg.attention, cfg.moe
    period = a.layer_pattern
    kinds = [WINDOW if period[i % len(period)] == "L" else "full_attention"
             for i in range(len(file["layer_types"]))]
    return {"num_key_value_heads": a.num_kv_heads, "vocab_size": cfg.vocab_size,
            "rms_norm_eps": cfg.norm_eps,
            "hidden_act": {"swiglu": "silu"}.get(cfg.act, cfg.act),
            "tie_word_embeddings": cfg.tie_embeddings,
            "num_experts": moe.num_experts, "num_experts_per_tok": moe.top_k,
            "moe_intermediate_size": moe.expert_ffn_dim,
            # the program renormalises the top-k weights, always
            "norm_topk_prob": True,
            "attention_bias": False,
            "mlp_layer_types": ["sparse" if moe.num_shared_experts == 0 else "shared"]
            * len(file["mlp_layer_types"]),
            "sliding_window": a.sliding_window, "layer_types": kinds,
            "rope_parameters": _rope_parameters(cfg)}


def _windows(file: dict) -> list:
    """Each served layer's key window (``None``: full attention)."""
    return [file["sliding_window"] if kind == WINDOW else None
            for kind in file["layer_types"][:file["num_hidden_layers"]]]


def matmul_params(file: dict) -> int:
    """Weights one token multiplies through in the layer stack: q, k, v and
    o, the router, and its top-k experts' three matrices."""
    d, h, kv, hd = (file["hidden_size"], file["num_attention_heads"],
                    file["num_key_value_heads"], file["head_dim"])
    e, k, f = file["num_experts"], file["num_experts_per_tok"], file["moe_intermediate_size"]
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + d * e + k * 3 * d * f
    return file["num_hidden_layers"] * per_layer


def ssa_ops(file: dict, p: int, keys: int) -> int:
    """Useful SSA operations of the query at position ``p`` over the first
    ``keys`` cached tokens, all layers: eq. 5 and eq. 6 counts (hd per
    visible key each, 2 operations) per head and time step, where a window
    layer's query sees at most ``sliding_window`` keys."""
    per_key = file["num_attention_heads"] * file["attention"]["time_steps"] * 4 * file["head_dim"]
    visible = min(p + 1, keys)
    return sum(per_key * (visible if w is None else min(visible, w))
               for w in _windows(file))


def token_ops(file: dict, p: int, keys: int, logits: bool) -> int:
    """Model operations of one token at position ``p`` over ``keys`` keys:
    active weights at 2 per weight, attention, and the head when read."""
    ops = 2 * matmul_params(file) + ssa_ops(file, p, keys)
    return ops + (2 * head_params(file) if logits else 0)


def expert_call(file: dict, pairs: int, experts: int) -> tuple[float, float]:
    """(operations, bytes) of one expert layer's three grouped matmuls:
    ``6 d f`` per routed pair; each expert with rows read once (three
    ``d x f`` bf16 matrices), and each call's activations in and out."""
    d, f = file["hidden_size"], file["moe_intermediate_size"]
    ops = 6 * d * f * pairs
    nbytes = 2 * (3 * d * f * experts + pairs * (3 * d + 3 * f))
    return float(ops), float(nbytes)


def kv_snapshot(engine, records, layers: int) -> list:
    """The packed K/V spike planes the engine holds for in-flight requests:
    (record, tokens written, {"ks", "vs"}) with uint32 arrays (layers,
    rows, T, H_kv, words) over the last ``min(rows, sliding_window)``
    positions, which a window layer's ring still holds.  Layer ``l`` is
    step ``l // P`` of pattern slot ``l % P``; each page row is placed by
    the position it caches, so a ring's rolled rows come out in order."""
    slot_of = {id(req): slot for slot, req in engine.active.items()}
    period = len(engine.cache)
    window = engine.model.cfg.attention.sliding_window
    out = []
    for rec in records:
        slot = slot_of[id(rec.req)]
        rows = int(engine.slot_pos[slot])
        keep = min(rows, window)
        pages = np.asarray(engine.tables.pages[slot], np.int32)
        planes = {"ks": [], "vs": []}
        for layer in range(layers):
            pool = engine.cache[layer % period]
            step = layer // period
            pos = np.asarray(pool["pos"][step, pages]).reshape(-1)
            at = pos - (rows - keep)
            mine = (at >= 0) & (at < keep)
            for name in ("ks", "vs"):
                leaf = np.asarray(pool[name][step, pages])
                leaf = leaf.reshape((-1,) + leaf.shape[2:])
                held = np.zeros((keep,) + leaf.shape[1:], leaf.dtype)
                held[at[mine]] = leaf[mine]
                planes[name].append(held)
        tokens = np.concatenate([rec.planned.prompt,
                                 np.asarray(rec.req.out_tokens[:-1], np.int32)])
        out.append((rec, tokens[:rows], {k: np.stack(v) for k, v in planes.items()}))
    return out
