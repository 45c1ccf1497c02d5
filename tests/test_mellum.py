"""mellum2-12b-a2.5b served through the packed spiking engine, at smoke size.

What the configuration brings to the system, each pinned here on the CPU
with seeded weights: YaRN RoPE on the full-attention layers, a dropless
top-8 expert layer whose result per token does not depend on how tokens
are batched, chunked prefill across the 16-token window (ring of window +
one page), the expert stats on traced events, and agreement of the
served logits with the plain reference's full forward pass.
"""
import dataclasses
import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import window_ring
from repro.configs import get_config, get_smoke_config, with_overrides
from repro.kernels.moe_gmm import moe_gmm, moe_gmm_ref
from repro.models import build_model
from repro.models.blocks import apply_rope, moe_apply, moe_dropless, yarn_inv_freq
from repro.obs import Tracer
from repro.serving import Request, ServingEngine

ARCH = "mellum2-12b-a2.5b"
ROOT = Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=None)
def _model(backend="xla", layout="paged"):
    cfg = with_overrides(
        get_smoke_config(ARCH), attention__impl="ssa",
        attention__spike_storage="packed", attention__cache_layout=layout,
        attention__backend=backend)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(7))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).astype(np.int32) for n in lengths]


def _serve(lengths, *, backend="xla", tracer=None, sampler=None, max_new=5,
           **engine_kw):
    _, model, params = _model(backend)
    kw = {"sampler": sampler} if sampler is not None else {}
    eng = ServingEngine(model, params, num_slots=2, max_seq=32, page_size=4,
                        tracer=tracer, **kw, **engine_kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new, seed=1000 + i)
            for i, p in enumerate(_prompts(lengths))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_ticks=200)
    return [r.out_tokens for r in reqs], eng


# ---------------------------------------------------------------------------
# configuration and RoPE
# ---------------------------------------------------------------------------
def test_published_configuration():
    cfg = get_config(ARCH)
    a = cfg.attention
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (28, 2304, 98304)
    assert (a.num_heads, a.num_kv_heads, a.head_dim) == (32, 4, 128)
    assert a.layer_pattern == "LLLG" and a.sliding_window == 1024
    assert (cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.expert_ffn_dim) == (64, 8, 896)
    assert cfg.moe.num_shared_experts == 0 and cfg.d_ff == 7168
    assert window_ring(1024, 16) == 1040


def test_yarn_frequencies_at_published_values():
    """low = floor(18.08) = 18, high = ceil(34.98) = 35: pairs up to 18
    keep theta^(-2i/hd), pairs from 35 on are divided by 16, a linear ramp
    between; cos and sin carry the attention factor."""
    yarn = get_config(ARCH).attention.global_yarn
    hd, theta = 128, 500_000.0

    def corr(r):
        return hd * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(theta))

    assert math.floor(corr(32)) == 18 and math.ceil(corr(1)) == 35
    inv = yarn_inv_freq(hd, theta, yarn)
    extrap = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ramp = np.clip((np.arange(64) - 18) / 17, 0, 1)
    np.testing.assert_allclose(inv, extrap / 16 * ramp + extrap * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:19], extrap[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], extrap[35:] / 16, rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 1, hd))
    pos = jnp.asarray([[0, 777]], jnp.int32)
    out = np.asarray(apply_rope(x, pos, theta, yarn))
    af = np.float32(yarn.attention_factor)
    np.testing.assert_allclose(out[0, 0], np.asarray(x)[0, 0] * af, rtol=1e-6)
    # the angle as the program forms it, in float32
    ang = (np.float32(777) * inv).astype(np.float64)
    x1, x2 = np.asarray(x)[0, 1, 0, :64], np.asarray(x)[0, 1, 0, 64:]
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)]) * af
    np.testing.assert_allclose(out[0, 1, 0], want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the dropless expert layer
# ---------------------------------------------------------------------------
def _moe_params():
    cfg, _, params = _model()
    return cfg.moe, jax.tree.map(lambda a: a[0], params["slots"][0])["moe"]


def _per_token(p, moe, x):
    """Each token through its own top-k experts, one at a time."""
    out = []
    for t in np.asarray(x).reshape(-1, x.shape[-1]):
        probs = jax.nn.softmax(jnp.dot(t, p["router"], precision="highest"))
        top_p, top_i = jax.lax.top_k(probs, moe.top_k)
        top_p = top_p / top_p.sum()
        y = 0.0
        for w, e in zip(np.asarray(top_p), np.asarray(top_i)):
            h = jax.nn.silu(t @ p["wg"][e]) * (t @ p["wi"][e])
            y = y + w * (h @ p["wo"][e])
        out.append(y)
    return np.stack(out).reshape(x.shape)


def test_dropless_routing_is_per_token():
    """One-shot == chunked == token-by-token, bit for bit, and equal to a
    per-token loop over its top-8 experts (float32 sums in another order:
    rtol 1e-5)."""
    moe, p = _moe_params()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 64))
    live = jnp.ones((2, 16), bool)
    whole, stats = moe_dropless(p, x, moe, live)
    chunks = jnp.concatenate(
        [moe_dropless(p, x[:, i:i + 4], moe, live[:, i:i + 4])[0]
         for i in range(0, 16, 4)], axis=1)
    # one token per row at a time, as a decode tick runs them
    tokens = jnp.concatenate(
        [moe_dropless(p, x[:, i:i + 1], moe, live[:, i:i + 1])[0]
         for i in range(16)], axis=1)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(chunks))
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(tokens))
    np.testing.assert_allclose(np.asarray(whole), _per_token(p, moe, x),
                               rtol=1e-5, atol=1e-6)
    assert int(stats[0]) == 32 * moe.top_k
    # a dead (pad) token routes nowhere and comes out 0
    out, st = moe_dropless(p, x, moe, live.at[0, 3].set(False))
    assert not np.asarray(out[0, 3]).any() and int(st[0]) == 31 * moe.top_k
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(whole[1]))


def test_no_pair_dropped_when_experts_take_every_token():
    """A router that sends every token of a 16-token chunk to the same 8
    experts: each gets 16 rows, more than the capacity dispatch's 10
    slots (which drops), and the dropless layer still computes them all."""
    moe, p = _moe_params()
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, :8].set(1.0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 16, 64)))
    out, stats = moe_apply(p, x, moe, "swiglu", valid=jnp.ones((1, 16), bool))
    assert stats.tolist() == [16 * moe.top_k, moe.top_k]
    want = _per_token(p, moe, x)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)
    dropped, _ = moe_apply(p, x, moe, "swiglu")     # training: capacity 10
    assert not np.allclose(np.asarray(dropped), want, rtol=1e-3)


@pytest.mark.parametrize("sizes", [[5, 0, 60, 3, 0, 40], [0, 0, 0, 0, 0, 100]],
                         ids=["mixed", "one-expert"])
def test_moe_gmm_matches_reference(sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(5), (140, 64))
    w = jax.random.normal(jax.random.PRNGKey(6), (6, 64, 32))
    got = moe_gmm(x, w, sizes, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(moe_gmm_ref(x, w, sizes)),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[int(sizes.sum()):]).any()


# ---------------------------------------------------------------------------
# chunked prefill across the window, expert stats on traced events
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _wrap_runs():
    """A 29-token prompt over the smoke window 16 (ring 20: chunks
    [16, 20) and [20, 24) wrap it) beside a 7-token one, chunked and
    traced against the one-shot engine."""
    lengths = [29, 7]
    tr = Tracer()
    chunked, eng = _serve(lengths, tracer=tr)
    one_shot, _ = _serve(lengths, prefill_chunk=0)
    return chunked, one_shot, eng, tr


def test_chunks_across_the_window_wrap_match_one_shot():
    chunked, one_shot, eng, tr = _wrap_runs()
    assert chunked == one_shot
    assert eng.stats()["chunked_prefills"] == 2
    spans = [(e.data["c0"], e.data["c1"]) for e in tr.events("prefill_chunk")
             if e.uid == 0]
    assert spans == [(c, min(c + 4, 29)) for c in range(0, 29, 4)]
    assert not [e for e in tr.events("compile") if e.data.get("fn") == "prefill"]


def test_traced_events_carry_expert_stats():
    _, _, eng, tr = _wrap_runs()
    layers, k, slots = 4, 8, 2
    chunks = tr.events("prefill_chunk")
    ticks = tr.events("decode_tick")
    assert chunks and ticks
    for e in chunks:
        real = e.data["c1"] - e.data["c0"]
        assert e.data["moe_pairs"] == [real * k] * layers
        assert all(1 <= n <= 16 for n in e.data["moe_experts"])
    for e in ticks:
        assert e.data["moe_pairs"] == [slots * k] * layers
        assert all(k <= n <= 16 for n in e.data["moe_experts"])


def test_served_logits_match_the_reference():
    """Paged packed cache, chunked prefill and the fused kernels (interpret
    mode): the logits of every served position of a prompt longer than the
    window agree with the plain reference's full forward pass.  Tolerance
    1e-5: float32 sums in another order (grouped matmul and fused kernel
    against the reference's dense expert sum); no spike differs."""
    sys.path.insert(0, str(ROOT))
    from bench.reference.moe_lm import MoELMReference

    cfg, _, _ = _model("fused")
    a = cfg.attention
    y = a.global_yarn
    ref = MoELMReference({
        "hidden_size": cfg.d_model, "num_attention_heads": a.num_heads,
        "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
        "rms_norm_eps": cfg.norm_eps, "num_experts": cfg.moe.num_experts,
        "num_experts_per_tok": cfg.moe.top_k,
        "moe_intermediate_size": cfg.moe.expert_ffn_dim, "norm_topk_prob": True,
        "sliding_window": a.sliding_window,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "rope_parameters": {
            "full_attention": {"rope_type": "yarn", "rope_theta": a.rope_theta,
                               **dataclasses.asdict(y)},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": a.rope_theta}},
        "attention": {"time_steps": a.ssa_time_steps}, "torch_dtype": "float32",
    })
    seen = []

    def sampler(key, logits):
        seen.append(np.asarray(logits))
        return jnp.argmax(logits, -1).astype(jnp.int32)

    (out,), _ = _serve([23], backend="fused", sampler=sampler)
    toks = np.concatenate([_prompts([23])[0], out[:-1]])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(jax.random.PRNGKey(7), toks, 1000))[22:]
    got = np.stack([s.reshape(-1, s.shape[-1])[0] for s in seen])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
