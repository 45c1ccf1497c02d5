"""Operations and bytes of the served work, counted from logical shapes.

The counts are the algorithm's, not a kernel's: one query row per decode
token, the keys a query may see and no more, spike trains at one bit per
spike (the packed cache's words hold exactly that).  Padding a kernel adds
(query tiles, page tiles, lane padding) is not work, so a faster kernel
cannot make a count wrong, and a roofline share built on them cannot pass
100%.

An operation is a multiply or an add (a multiply-accumulate counts 2).
``cfg`` is a configuration file (``bench/configs/<name>.json``).
"""
from __future__ import annotations

from . import families


def _heads(cfg):
    a = cfg["attention"]
    return (cfg["num_attention_heads"], cfg.get("num_key_value_heads",
                                                cfg["num_attention_heads"]),
            cfg["head_dim"], a["time_steps"])


def matmul_params(cfg) -> int:
    """Weights one token multiplies through in the layer stack."""
    return families.load(cfg["family"]).matmul_params(cfg)


def head_params(cfg) -> int:
    """Weights of the output head."""
    return families.load(cfg["family"]).head_params(cfg)


def ssa_ops(cfg, visible: int) -> int:
    """Useful SSA operations of one query token over ``visible`` keys, all
    layers: per head and time step, the eq. 5 score counts (hd per key)
    and the eq. 6 output counts (hd per key), 2 operations each."""
    h, _, hd, t = _heads(cfg)
    return cfg["num_hidden_layers"] * h * t * 4 * hd * visible


def token_ops(cfg, visible: int, logits: bool) -> int:
    """Model operations of one token: projections and MLP, attention over
    ``visible`` keys, and the head when the token's logits are read."""
    ops = 2 * matmul_params(cfg) + ssa_ops(cfg, visible)
    return ops + (2 * head_params(cfg) if logits else 0)


def _spike_bytes(cfg, tokens: int, heads: int) -> float:
    """Bytes of ``tokens`` tokens' spike trains at one bit per spike."""
    _, _, hd, t = _heads(cfg)
    return tokens * t * heads * hd / 8


def ssa_call(cfg, q_positions, keys: int) -> tuple[float, float]:
    """(operations, bytes) of one SSA kernel call over one sequence, summed
    over the layers: queries at absolute ``q_positions`` attend causally
    over the first ``keys`` cached tokens (bidirectional, all ``keys``,
    for a non-causal model).  Bytes: the K and V trains of the keys read
    once, the query trains in and the output trains out."""
    h, kv, _, _ = _heads(cfg)
    causal = cfg["attention"]["causal"]
    vis = sum(min(p + 1, keys) if causal else keys for p in q_positions)
    n_q = len(q_positions)
    ops = ssa_ops(cfg, 1) * vis
    per_layer = 2 * _spike_bytes(cfg, keys, kv) + 2 * _spike_bytes(cfg, n_q, h)
    return float(ops), float(cfg["num_hidden_layers"] * per_layer)
