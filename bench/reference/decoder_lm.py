"""Plain float32 reference of the spiking decoder LM (codeqwen-style).

Per layer: RMSNorm -> Q/K/V projections -> RoPE -> LIF rate coding -> SSA
(eq. 5/6, causal) -> RMSNorm of the spike rates -> output projection ->
residual; RMSNorm -> SwiGLU MLP -> residual.  Then a final RMSNorm and the
LM head.  Weights are drawn from the seed as the configuration states
(normal draws scaled by 1/sqrt(fan-in), 0.02 for the embedding and the
head, stored in bfloat16); arithmetic is float32, and values are stored
between operations as ``numerics`` says for the mode.

The reference runs teacher-forced over a prompt and the tokens that were
served for it, one layer at a time so that only one layer's weights live
on the device, and reads how far below its best logit each served token
lies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .numerics import head_mode, layer_mode, matmul, store
from .spiking import layer_seed, spike_encode, ssa_attention


def _normal(key, shape, scale):
    # the barrier keeps XLA from folding the scale into the sampler
    return (jax.lax.optimization_barrier(jax.random.normal(key, shape))
            * np.float32(scale))


def _inv_sqrt(n: int) -> np.float32:
    return np.float32(1.0) / np.sqrt(np.float32(n))


class DecoderLMReference:
    def __init__(self, cfg: dict):
        self.d = int(cfg["hidden_size"])
        self.ff = int(cfg["intermediate_size"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.hd = int(cfg["head_dim"])
        self.layers = int(cfg["num_hidden_layers"])
        self.vocab = int(cfg["vocab_size"])
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.t_steps = int(cfg["attention"]["time_steps"])
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self._gen_layer = jax.jit(self._layer_weights, static_argnums=1)
        self._embed_w = jax.jit(self._embed_weights)
        self._head_w = jax.jit(self._head_weights)
        self._layer = jax.jit(self._layer_apply, static_argnums=5)
        self._kv = jax.jit(self._kv_spikes, static_argnums=3)
        self._head = jax.jit(self._head_gaps, static_argnums=(3, 4))

    # ---- weights, drawn from the seed as the configuration states ----
    def _keys(self, key):
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        return k_embed, jax.random.split(jax.random.fold_in(k_layers, 0),
                                         self.layers), k_head

    def _embed_weights(self, key):
        return _normal(self._keys(key)[0], (self.vocab, self.d), 0.02).astype(self.dtype)

    def _head_weights(self, key):
        return _normal(self._keys(key)[2], (self.d, self.vocab), 0.02).astype(self.dtype)

    def _layer_weights(self, key, layer: int):
        k = self._keys(key)[1][layer]
        ka, kf = jax.random.split(k)
        ks = jax.random.split(ka, 5)
        kq = self.heads * self.hd
        kkv = self.kv_heads * self.hd
        d, ff, dt = self.d, self.ff, self.dtype
        km = jax.random.split(kf, 3)
        return {
            "wq": _normal(ks[0], (d, kq), _inv_sqrt(d)).astype(dt),
            "wk": _normal(ks[1], (d, kkv), _inv_sqrt(d)).astype(dt),
            "wv": _normal(ks[2], (d, kkv), _inv_sqrt(d)).astype(dt),
            "wo": _normal(ks[3], (kq, d), _inv_sqrt(kq)).astype(dt),
            "wi": _normal(km[0], (d, ff), _inv_sqrt(d)).astype(dt),
            "wg": _normal(km[1], (d, ff), _inv_sqrt(d)).astype(dt),
            "wd": _normal(km[2], (ff, d), _inv_sqrt(ff)).astype(dt),
        }

    # ---- layer equations ----
    def _rms(self, x, eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    def _rope(self, x, positions):
        half = self.hd // 2
        freqs = 1.0 / (self.theta ** (jnp.arange(0, self.hd, 2, dtype=jnp.float32)
                                      / self.hd))
        ang = positions[:, None].astype(jnp.float32) * freqs     # (S, hd/2)
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _kv_spikes(self, w, x, positions, mode):
        """(T, S, H_kv, hd) K and V spike trains a layer caches for x."""
        s = x.shape[0]
        h = store(self._rms(x, self.eps), mode)
        k = matmul(h, w["wk"], mode).reshape(s, self.kv_heads, self.hd)
        v = matmul(h, w["wv"], mode).reshape(s, self.kv_heads, self.hd)
        k = store(self._rope(k, positions), mode)
        return spike_encode(k, self.t_steps), spike_encode(v, self.t_steps)

    def _layer_apply(self, w, x, positions, request_seed, layer, mode):
        s = x.shape[0]
        h = store(self._rms(x, self.eps), mode)
        q = matmul(h, w["wq"], mode).reshape(s, self.heads, self.hd)
        k = matmul(h, w["wk"], mode).reshape(s, self.kv_heads, self.hd)
        v = matmul(h, w["wv"], mode).reshape(s, self.kv_heads, self.hd)
        q = store(self._rope(q, positions), mode)
        k = store(self._rope(k, positions), mode)
        rates = ssa_attention(q, k, v, layer_seed(request_seed, layer), positions,
                              t_steps=self.t_steps, causal=True)
        o = store(self._rms(rates.reshape(s, self.heads * self.hd), 1e-6), mode)
        x = store(x + matmul(o, w["wo"], mode), mode)
        h = store(self._rms(x, self.eps), mode)
        g = matmul(h, w["wg"], mode)
        u = matmul(h, w["wi"], mode)
        m = store(store(jax.nn.silu(g), mode) * u, mode)
        return store(x + matmul(m, w["wd"], mode), mode)

    def _head_gaps(self, w, hidden, targets, base, others):
        """Gaps of each scored row below the ``base`` reference's best logit:
        of the served token (``served``), and of the token each other mode's
        logits rank first.  Rows with target -1 read 0."""
        def logits(x, mode):
            mode = head_mode(mode)
            h = store(self._rms(x, self.eps), mode)
            # the last product is read in float32 (the gap is measured there)
            return matmul(h, w, "int8" if mode == "int8" else "f32")

        ref = logits(hidden[base], base)
        best = ref.max(-1)
        live = targets >= 0
        at = jnp.take_along_axis(ref, jnp.maximum(targets, 0)[:, None], 1)[:, 0]
        gaps = {"served": jnp.where(live, best - at, 0.0)}
        for m in others:
            pick = jnp.argmax(logits(hidden[m], m), -1)
            at_m = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
            gaps[m] = jnp.where(live, best - at_m, 0.0)
        return gaps

    # ---- teacher-forced gaps ----
    def served_gaps(self, key, sequences, *, mode, others=(), pad_to=None):
        """Logit gaps of the served tokens, and of other modes' picks.

        ``sequences``: iterable of (prompt, served, request_seed).  Each is
        run over ``prompt + served[:-1]`` at positions 0.., padded to
        ``pad_to`` rows (pad positions -1 are masked, so one compiled layer
        serves every length).  ``mode``: the reference's numerics
        (``numerics.MODES``); ``others``: modes run beside it (a control).
        Returns one dict of numpy arrays per sequence: ``served`` (n,),
        each served token's gap below the reference's best logit at its
        position, and for each other mode the same for its first choice.
        """
        seqs = []
        for prompt, served, rseed in sequences:
            toks = np.concatenate([np.asarray(prompt, np.int32),
                                   np.asarray(served[:-1], np.int32)])
            n = len(toks)
            rows = pad_to or n
            if rows < n:
                raise ValueError(f"sequence of {n} tokens exceeds pad_to={rows}")
            t = np.zeros(rows, np.int32)
            t[:n] = toks
            pos = np.full(rows, -1, np.int32)
            pos[:n] = np.arange(n)
            tgt = np.full(rows, -1, np.int32)
            p_len = len(prompt)
            tgt[p_len - 1:p_len - 1 + len(served)] = np.asarray(served, np.int32)
            seqs.append((t, pos, np.uint32(rseed), tgt, p_len, len(served)))
        modes = (mode,) + tuple(m for m in others if m != mode)
        # modes whose layers run alike share one pass through the stack
        stack = {m: tuple(layer_mode(m, i) for i in range(self.layers)) for m in modes}
        runs = list(dict.fromkeys(stack.values()))
        embed = self._embed_w(key)
        hidden = {r: [jnp.take(embed, jnp.asarray(s[0]), axis=0).astype(jnp.float32)
                      for s in seqs] for r in runs}
        del embed
        for layer in range(self.layers):
            w = self._gen_layer(key, layer)
            for r in runs:
                hidden[r] = [self._layer(w, x, jnp.asarray(s[1]), s[2], layer, r[layer])
                             for x, s in zip(hidden[r], seqs)]
            del w
        head = self._head_w(key)
        out = []
        for i, s in enumerate(seqs):
            g = self._head(head, {m: hidden[stack[m]][i] for m in modes},
                           jnp.asarray(s[3]), mode, modes[1:])
            lo, n = s[4] - 1, s[5]
            out.append({k: np.asarray(v)[lo:lo + n] for k, v in g.items()})
        return out

    def kv_planes(self, key, sequences, *, layers: int, modes, pad_to=None):
        """Packed K/V spike planes of the first ``layers`` layers.

        ``sequences``: iterable of (tokens, request_seed), run at positions
        0.. and padded to ``pad_to`` rows as in :meth:`served_gaps`.
        Returns, per mode, one ``{"ks", "vs"}`` dict per sequence of uint32
        arrays (layers, S, T, H_kv, words) over its S real rows: bit ``j``
        of word ``w`` is channel ``32 w + j``, the layout of a packed cache.
        """
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
            for m in modes:
                for i, (_, pos, sd, n) in enumerate(seqs):
                    lm = layer_mode(m, layer)
                    ks, vs = self._kv(w, hidden[m][i], pos, lm)
                    out[m][i]["ks"].append(_pack(ks)[:n])
                    out[m][i]["vs"].append(_pack(vs)[:n])
                    if layer + 1 < layers:
                        hidden[m][i] = self._layer(w, hidden[m][i], pos, sd, layer, lm)
            del w
        return {m: [{k: np.stack(v) for k, v in d.items()} for d in out[m]]
                for m in modes}


Reference = DecoderLMReference


def _pack(spikes) -> np.ndarray:
    """(T, S, H, hd) 0/1 -> (S, T, H, ceil(hd/32)) uint32, little-endian."""
    bits = np.asarray(spikes).astype(np.uint32).transpose(1, 0, 2, 3)
    hd = bits.shape[-1]
    words = -(-hd // 32)
    bits = np.pad(bits, [(0, 0)] * 3 + [(0, words * 32 - hd)])
    bits = bits.reshape(*bits.shape[:-1], words, 32)
    return (bits << np.arange(32, dtype=np.uint32)).sum(-1, dtype=np.uint32)
