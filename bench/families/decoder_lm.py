"""Decoder LMs: chunked prefill into pages, decode through the packed paged cache."""
from __future__ import annotations

import numpy as np

from ..reference.decoder_lm import Reference  # noqa: F401
from .. import traffic


def stated_sizes(file: dict, cfg, model) -> dict:
    a = cfg.attention
    return {"num_key_value_heads": a.num_kv_heads, "vocab_size": cfg.vocab_size,
            "rope_theta": a.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "hidden_act": {"swiglu": "silu"}.get(cfg.act, cfg.act),
            "tie_word_embeddings": cfg.tie_embeddings}


def init_params(model, key):
    # the model's init is one jitted program, drawing layer by layer
    return model.init(key)


def vocab(file: dict) -> int:
    return file["vocab_size"]


def max_total(file: dict) -> int:
    return file["engine"]["max_seq"] - 2


def reference_kwargs(file: dict) -> dict:
    # one compiled reference layer serves every length
    return {"pad_to": file["engine"]["max_seq"]}


def matmul_params(file: dict) -> int:
    d, ff = file["hidden_size"], file["intermediate_size"]
    h, kv, hd = (file["num_attention_heads"], file["num_key_value_heads"],
                 file["head_dim"])
    # q, k, v and o; a three-matrix SwiGLU
    return file["num_hidden_layers"] * (d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff)


def head_params(file: dict) -> int:
    return file["hidden_size"] * file["vocab_size"]


def kv_snapshot(engine, records, layers: int) -> list:
    """The packed K/V spike planes the engine holds for in-flight requests:
    (record, tokens written, {"ks", "vs"}) with uint32 arrays (layers,
    rows, T, H_kv, words), read from its page pool through the request's
    block table."""
    slot_of = {id(req): slot for slot, req in engine.active.items()}
    pool = engine.cache[0]
    out = []
    for rec in records:
        slot = slot_of[id(rec.req)]
        rows = int(engine.slot_pos[slot])
        pages = np.asarray(engine.tables.pages[slot], np.int32)
        planes = {}
        for name in ("ks", "vs"):
            leaf = np.asarray(pool[name][:layers, pages])
            leaf = leaf.reshape((layers, -1) + leaf.shape[3:])
            planes[name] = leaf[:, :rows]
        tokens = np.concatenate([rec.planned.prompt,
                                 np.asarray(rec.req.out_tokens[:-1], np.int32)])
        out.append((rec, tokens[:rows], planes))
    return out


def warmup_prompts(file: dict, plan: traffic.Plan) -> list[tuple[int, int]]:
    """Every (chunk bucket, block-table width) a prompt's chunks reach, and
    every decode block-table width between the shortest prompt's first
    tick and the longest request's last, by as few requests as cover them."""
    eng = file["engine"]
    page, chunk, max_w = eng["page_size"], eng["prefill_chunk"], eng["max_seq"] // eng["page_size"]
    lens = sorted({len(r.prompt) for r in plan.requests})
    totals = [len(r.prompt) + r.max_new_tokens for r in plan.requests]

    def width(rows):
        return min(_pow2(-(-rows // page)), max_w)

    def chunk_sigs(p):
        sigs = set()
        for c0 in range(0, p, chunk):
            c1 = min(c0 + chunk, p)
            sigs.add((min(_pow2(c1 - c0), chunk), width(c1)))
        return sigs

    need = set().union(*(chunk_sigs(p) for p in lens))
    picks = []
    for p in sorted(lens, key=lambda p: -len(chunk_sigs(p))):
        if chunk_sigs(p) & need:
            picks.append(p)
            need -= chunk_sigs(p)
    w_lo, w_hi = width(min(lens) + 1), width(max(totals) - 1)
    have = {width(p + 1) for p in picks}
    out = [(p, 2) for p in picks]
    w = w_lo
    while w <= w_hi:
        if w not in have:
            p = min(max((w // 2) * page, 1), max(lens))
            while width(p + 1) < w:
                p += 1
            out.append((p, 2))
        w *= 2
    return out


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p
