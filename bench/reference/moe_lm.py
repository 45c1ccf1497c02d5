"""Plain float32 reference of the spiking expert LM (mellum2-style).

Per layer: RMSNorm -> Q/K/V projections -> RoPE (YaRN on full-attention
layers, plain on window layers) -> LIF rate coding -> SSA (eq. 5/6, causal,
keys ``kp > qp - window`` on window layers) -> RMSNorm of the spike rates
-> output projection -> residual; RMSNorm -> softmax router over every
expert, top-k, the k weights renormalised to sum to 1 -> each expert's
SwiGLU, weighted and summed -> residual.  Then a final RMSNorm and the LM
head.  Each token's k experts are computed for it: no capacity, nothing
dropped.

Weights are drawn from the seed as the program draws them: layer ``l`` is
the ``l // P``-th draw of pattern slot ``l % P`` (``P`` the period of
``layer_types``), the router in float32 and the rest in the stated dtype.
The router runs in float32 at ``highest`` precision in every mode; the
other products follow ``numerics``.  Teacher-forced over a prompt and its
served tokens, one layer at a time, as ``decoder_lm``'s reference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .decoder_lm import DecoderLMReference, _inv_sqrt, _normal, _pack
from .numerics import layer_mode, matmul, store
from .spiking import (
    layer_seed,
    spike_encode,
    step_seeds,
    uniform,
    SALT_A,
    SALT_S,
    POS_STRIDE_A,
    POS_STRIDE_S,
)

WINDOW, FULL = "sliding_attention", "full_attention"
# (token, expert) pairs a block of the reference's expert loop holds
PAIR_BLOCK = 128


def period(kinds) -> int:
    """Shortest repeating unit of a list of layer kinds."""
    for p in range(1, len(kinds) + 1):
        if all(kinds[i] == kinds[i % p] for i in range(len(kinds))):
            return p
    return len(kinds)


def yarn_inv_freq(head_dim: int, rope: dict) -> np.ndarray:
    """HF ``_compute_yarn_parameters`` frequencies, float64 -> float32."""
    theta, dim = float(rope["rope_theta"]), head_dim
    extrap = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    interp = extrap / float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (interp * ramp + extrap * (1.0 - ramp)).astype(np.float32)


def ssa_head(qs, ks, vs, seed, pos, window):
    """One head, one time step of causal SSA over keys ``kp > qp - window``
    (``window`` None: every earlier key); as ``spiking.ssa_head``."""
    hd = qs.shape[-1]
    counts = jnp.dot(qs, ks.T, precision="default", preferred_element_type=jnp.float32)
    valid = (pos[None, :] >= 0) & (pos[:, None] >= 0) & (pos[None, :] <= pos[:, None])
    if window is not None:
        valid &= pos[None, :] > pos[:, None] - window
    p = jnp.maximum(pos, 0).astype(jnp.uint32)
    u_s = uniform(seed ^ SALT_S, p[:, None] * POS_STRIDE_S + p[None, :])
    s = (valid & (u_s * np.float32(hd) < counts)).astype(jnp.float32)
    counts_a = jnp.dot(s, vs, precision="default", preferred_element_type=jnp.float32)
    visible = jnp.maximum(valid.sum(-1), 1).astype(jnp.float32)[:, None]
    col = jnp.arange(hd, dtype=jnp.uint32)
    u_a = uniform(seed ^ SALT_A, p[:, None] * POS_STRIDE_A + col[None, :])
    return (u_a * visible < counts_a).astype(jnp.float32)


def ssa_attention(q, k, v, seed_l, positions, *, t_steps, window):
    """Grouped-query SSA with rate decoding, as ``spiking.ssa_attention``."""
    heads, kv_heads = q.shape[1], k.shape[1]
    kv_of = jnp.arange(heads) // (heads // kv_heads)
    qs = spike_encode(q, t_steps).transpose(0, 2, 1, 3)
    ks = spike_encode(k, t_steps).transpose(0, 2, 1, 3)[:, kv_of]
    vs = spike_encode(v, t_steps).transpose(0, 2, 1, 3)[:, kv_of]
    seeds = step_seeds(seed_l, t_steps, heads)
    per_head = jax.vmap(lambda a, b, c, sd: ssa_head(a, b, c, sd, positions, window))
    out = jax.lax.map(lambda x: per_head(*x), (qs, ks, vs, seeds))
    return out.mean(axis=0).transpose(1, 0, 2)


class MoELMReference(DecoderLMReference):
    def __init__(self, cfg: dict):
        self.d = int(cfg["hidden_size"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.hd = int(cfg["head_dim"])
        self.layers = int(cfg["num_hidden_layers"])
        self.vocab = int(cfg["vocab_size"])
        self.eps = float(cfg["rms_norm_eps"])
        self.experts = int(cfg["num_experts"])
        self.top_k = int(cfg["num_experts_per_tok"])
        self.ff = int(cfg["moe_intermediate_size"])
        self.norm_topk = bool(cfg["norm_topk_prob"])
        self.window = int(cfg["sliding_window"])
        self.kinds = list(cfg["layer_types"])
        self.period = period(self.kinds)
        self.steps = self.layers // self.period
        ropes = cfg["rope_parameters"]
        self.theta = {kind: float(ropes[kind]["rope_theta"]) for kind in (WINDOW, FULL)}
        self.yarn = {kind: ropes[kind] if ropes[kind]["rope_type"] == "yarn" else None
                     for kind in (WINDOW, FULL)}
        self.t_steps = int(cfg["attention"]["time_steps"])
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self._gen_layer = jax.jit(self._layer_weights, static_argnums=1)
        self._embed_w = jax.jit(self._embed_weights)
        self._head_w = jax.jit(self._head_weights)
        self._layer_jit = jax.jit(self._layer_apply, static_argnums=(5, 6))
        self._kv_jit = jax.jit(self._kv_spikes, static_argnums=(3, 4))
        self._head = jax.jit(self._head_gaps, static_argnums=(3, 4))

    # ---- weights, drawn as the program draws them ----
    def _keys(self, key):
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        return k_embed, k_layers, k_head

    def _layer_weights(self, key, layer: int):
        slot, step = layer % self.period, layer // self.period
        k = jax.random.split(jax.random.fold_in(self._keys(key)[1], slot),
                             self.steps)[step]
        ka, kf = jax.random.split(k)
        ks = jax.random.split(ka, 5)
        km = jax.random.split(kf, 5)
        kq, kkv = self.heads * self.hd, self.kv_heads * self.hd
        d, ff, e, dt = self.d, self.ff, self.experts, self.dtype
        return {
            "wq": _normal(ks[0], (d, kq), _inv_sqrt(d)).astype(dt),
            "wk": _normal(ks[1], (d, kkv), _inv_sqrt(d)).astype(dt),
            "wv": _normal(ks[2], (d, kkv), _inv_sqrt(d)).astype(dt),
            "wo": _normal(ks[3], (kq, d), _inv_sqrt(kq)).astype(dt),
            "router": _normal(km[0], (d, e), _inv_sqrt(d)),
            "wi": _normal(km[1], (e, d, ff), _inv_sqrt(d)).astype(dt),
            "wg": _normal(km[2], (e, d, ff), _inv_sqrt(d)).astype(dt),
            # a division, as the program draws it
            "wd": (jax.lax.optimization_barrier(jax.random.normal(km[3], (e, ff, d)))
                   / jax.lax.optimization_barrier(np.sqrt(np.float32(ff)))).astype(dt),
        }

    # ---- layer equations ----
    def _rope_kind(self, x, positions, kind):
        half = self.hd // 2
        yarn = self.yarn[kind]
        if yarn is None:
            freqs = 1.0 / (self.theta[kind] ** (
                jnp.arange(0, self.hd, 2, dtype=jnp.float32) / self.hd))
        else:
            freqs = jnp.asarray(yarn_inv_freq(self.hd, yarn))
        ang = positions[:, None].astype(jnp.float32) * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        if yarn is not None:
            scale = np.float32(yarn["attention_factor"])
            cos, sin = cos * scale, sin * scale
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _kv_spikes(self, w, x, positions, kind, mode):
        s = x.shape[0]
        h = store(self._rms(x, self.eps), mode)
        k = matmul(h, w["wk"], mode).reshape(s, self.kv_heads, self.hd)
        v = matmul(h, w["wv"], mode).reshape(s, self.kv_heads, self.hd)
        k = store(self._rope_kind(k, positions, kind), mode)
        return spike_encode(k, self.t_steps), spike_encode(v, self.t_steps)

    def _experts(self, w, h, mode):
        """Each token's top-k experts, weighted by their renormalised gates
        and summed in float32 in rank order.  The (token, expert) pairs are
        sorted by expert and run in blocks of ``PAIR_BLOCK`` pairs of one
        expert each, so an expert's matrices meet only its own tokens: a
        row's result is what it would be with every expert applied and the
        others weighted 0, at a k-th of the work."""
        s, e, k, blk = h.shape[0], self.experts, self.top_k, PAIR_BLOCK
        logits = jnp.matmul(h, w["router"], precision="highest")
        top_p, top_i = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        if self.norm_topk:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        flat = top_i.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=e)
        ends = jnp.cumsum(sizes)
        # expert i's pairs [ends[i] - sizes[i], ends[i]) fill ceil(size / blk)
        # blocks; block j belongs to the expert whose block range holds j
        blocks = -(-sizes // blk)
        blk_end = jnp.cumsum(blocks)
        rows = jnp.pad(h[order // k], ((0, blk), (0, 0)))

        def block(j, acc):
            ex = jnp.searchsorted(blk_end, j, side="right")
            start = ends[ex] - sizes[ex] + (j - blk_end[ex] + blocks[ex]) * blk
            x = jax.lax.dynamic_slice_in_dim(rows, start, blk)
            g = matmul(x, w["wg"][ex], mode)
            u = matmul(x, w["wi"][ex], mode)
            m = store(store(jax.nn.silu(g), mode) * u, mode)
            y = matmul(m, w["wd"][ex], mode)
            r = start + jnp.arange(blk)
            return acc.at[r].add(jnp.where((r < ends[ex])[:, None], y, 0.0))

        y = jax.lax.fori_loop(0, blk_end[-1], block, jnp.zeros_like(rows))
        back = jnp.zeros(s * k, jnp.int32).at[order].set(jnp.arange(s * k, dtype=jnp.int32))
        y = y[back].reshape(s, k, -1)
        out = top_p[:, 0:1] * y[:, 0]
        for r in range(1, k):
            out = out + top_p[:, r:r + 1] * y[:, r]
        return store(out, mode)

    def _layer_apply(self, w, x, positions, request_seed, layer, kind, mode):
        s = x.shape[0]
        h = store(self._rms(x, self.eps), mode)
        q = matmul(h, w["wq"], mode).reshape(s, self.heads, self.hd)
        k = matmul(h, w["wk"], mode).reshape(s, self.kv_heads, self.hd)
        v = matmul(h, w["wv"], mode).reshape(s, self.kv_heads, self.hd)
        q = store(self._rope_kind(q, positions, kind), mode)
        k = store(self._rope_kind(k, positions, kind), mode)
        rates = ssa_attention(q, k, v, layer_seed(request_seed, layer), positions,
                              t_steps=self.t_steps,
                              window=self.window if kind == WINDOW else None)
        o = store(self._rms(rates.reshape(s, self.heads * self.hd), 1e-6), mode)
        x = store(x + matmul(o, w["wo"], mode), mode)
        h = store(self._rms(x, self.eps), mode)
        return store(x + self._experts(w, h, mode), mode)

    def _layer(self, w, x, positions, request_seed, layer, mode):
        return self._layer_jit(w, x, positions, request_seed, layer,
                               self.kinds[layer], mode)

    # ---- teacher-forced logits (tests) ----
    def logits(self, key, tokens, request_seed, *, mode="f32"):
        """(S, vocab) float32 logits of ``tokens`` at positions 0.. ."""
        x = jnp.take(self._embed_w(key), jnp.asarray(tokens), axis=0).astype(jnp.float32)
        pos = jnp.arange(len(tokens), dtype=jnp.int32)
        for layer in range(self.layers):
            x = self._layer(self._gen_layer(key, layer), x, pos,
                            np.uint32(request_seed), layer, mode)
        h = store(self._rms(x, self.eps), mode)
        return matmul(h, self._head_w(key), mode)

    def kv_planes(self, key, sequences, *, layers: int, modes, pad_to=None):
        """Packed K/V spike planes of the first ``layers`` layers, as
        ``decoder_lm``'s, over the last ``min(n, window)`` rows of each
        sequence: the rows a window layer's ring still holds, compared in
        every layer alike."""
        seqs = []
        for toks, sd in sequences:
            n = len(toks)
            rows = pad_to or n
            t = np.zeros(rows, np.int32)
            t[:n] = toks
            pos = np.full(rows, -1, np.int32)
            pos[:n] = np.arange(n)
            seqs.append((t, jnp.asarray(pos), np.uint32(sd), n))
        embed = self._embed_w(key)
        hidden = {m: [jnp.take(embed, jnp.asarray(s[0]), axis=0).astype(jnp.float32)
                      for s in seqs] for m in modes}
        del embed
        out = {m: [{"ks": [], "vs": []} for _ in seqs] for m in modes}
        for layer in range(layers):
            w = self._gen_layer(key, layer)
            kind = self.kinds[layer]
            for m in modes:
                for i, (_, pos, sd, n) in enumerate(seqs):
                    lm = layer_mode(m, layer)
                    ks, vs = self._kv_jit(w, hidden[m][i], pos, kind, lm)
                    lo = n - min(n, self.window)
                    out[m][i]["ks"].append(_pack(ks)[lo:n])
                    out[m][i]["vs"].append(_pack(vs)[lo:n])
                    if layer + 1 < layers:
                        hidden[m][i] = self._layer(w, hidden[m][i], pos, sd, layer, lm)
            del w
        return {m: [{k: np.stack(v) for k, v in d.items()} for d in out[m]]
                for m in modes}


Reference = MoELMReference
