"""Attention backend registry: resolution rules, cross-backend parity
(bit-exact xla vs fused, dense vs packed, at model level), statistical
equivalence with the historical threefry path, the no-unpack-in-decode HLO
guarantee, and serving-engine token identity across backends."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import (
    AttentionInvocation,
    available_backends,
    get_backend,
    register_backend,
    resolve_backend_name,
)
from repro.attention.spiking import folded_spike_trains, rate_decode
from repro.configs import get_smoke_config, with_overrides
from repro.models import build_model
from repro.models.blocks import attention_apply, attention_params
from repro.serving import Request, ServingEngine


def _ssa_cfg(backend="xla", storage="dense", arch="codeqwen15_7b", **extra):
    return with_overrides(
        get_smoke_config(arch),
        attention__impl="ssa",
        attention__backend=backend,
        attention__spike_storage=storage,
        **extra,
    )


# ---------------------------------------------------------------------------
# registry + resolution rules
# ---------------------------------------------------------------------------
def test_fused_lane_runs_in_interpret_mode(interpret_mode):
    """On the CPU CI lane the fused backends must fall back to interpret
    mode (not skip): every fused test in this module actually executed the
    Pallas kernel body."""
    import jax

    if jax.default_backend() != "tpu":
        assert interpret_mode


def test_builtin_backends_registered():
    assert set(available_backends()) >= {
        "ann-xla",
        "ssa-xla",
        "ssa-fused",
        "ssa-fused-packed",
        "spikformer-xla",
    }


@pytest.mark.parametrize(
    "impl,backend,storage,mode,platform,expected",
    [
        ("ann", "auto", "dense", "train", "cpu", "ann-xla"),
        ("ann", "auto", "dense", "decode", "tpu", "ann-xla"),
        ("spikformer", "xla", "dense", "train", "tpu", "spikformer-xla"),
        ("ssa", "auto", "dense", "train", "cpu", "ssa-xla"),
        ("ssa", "auto", "dense", "train", "tpu", "ssa-fused"),
        ("ssa", "xla", "dense", "decode", "tpu", "ssa-xla"),
        ("ssa", "fused", "dense", "decode", "cpu", "ssa-fused"),
        ("ssa", "fused", "packed", "prefill", "cpu", "ssa-fused"),
        ("ssa", "fused", "packed", "decode", "cpu", "ssa-fused-packed"),
        ("ssa", "auto", "packed", "decode", "tpu", "ssa-fused-packed"),
        ("ssa", "auto", "packed", "decode", "cpu", "ssa-xla"),
    ],
)
def test_resolution_rules(impl, backend, storage, mode, platform, expected):
    a = dataclasses.replace(
        get_smoke_config("codeqwen15_7b").attention,
        impl=impl,
        backend=backend,
        spike_storage=storage,
    )
    assert resolve_backend_name(a, mode, platform) == expected


def test_fused_backend_requires_ssa():
    a = dataclasses.replace(
        get_smoke_config("codeqwen15_7b").attention, impl="ann", backend="fused"
    )
    with pytest.raises(ValueError, match="fused"):
        resolve_backend_name(a, "train", "cpu")
    cfg = with_overrides(
        get_smoke_config("codeqwen15_7b"),
        attention__impl="ann",
        attention__backend="fused",
    )
    with pytest.raises(ValueError):
        build_model(cfg)
    with pytest.raises(ValueError):
        build_model(with_overrides(cfg, attention__backend="nope"))


def test_record_backends_sees_what_traced_calls_resolve():
    """``record_backends`` collects the backend every traced attention
    call resolved, by mode; outside the block nothing is recorded."""
    from repro.attention import record_backends

    cfg = _ssa_cfg(backend="fused", storage="packed")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((1, 4), jnp.int32),
             "positions": jnp.arange(4, dtype=jnp.int32)[None]}
    with record_backends() as rec:
        _, cache = model.prefill(params, batch, model.init_cache(1, 8))
        model.decode_step(
            params, {"tokens": jnp.ones((1, 1), jnp.int32),
                     "positions": jnp.full((1, 1), 4, jnp.int32)},
            cache, jnp.full((1,), 4, jnp.int32),
        )
    assert rec == {"prefill": {"ssa-fused"}, "decode": {"ssa-fused-packed"}}
    with record_backends() as rec:
        pass
    model.prefill(params, batch, model.init_cache(1, 8))
    assert rec == {}


def test_step_row_seeds_of_a_head_slice_match_the_full_expansion():
    """A head shard (``head0``) derives exactly its heads' streams — what
    lets ``head_sharded`` run the Pallas backends per shard bit-exactly."""
    from repro.attention import derive_step_row_seeds

    seeds = jnp.asarray([17, 23, 99], jnp.uint32)
    t, h, shards = 3, 8, 4
    full = derive_step_row_seeds(seeds, t, h).reshape(t, 3, h)
    for i in range(shards):
        part = derive_step_row_seeds(seeds, t, h // shards, i * h // shards)
        np.testing.assert_array_equal(
            np.asarray(part).reshape(t, 3, h // shards),
            np.asarray(full[:, :, i * h // shards:(i + 1) * h // shards]),
        )


# ---------------------------------------------------------------------------
# backend parity at model level (attention_apply orchestration included)
# ---------------------------------------------------------------------------
def _attn_block(cfg, key, b=2, s=8):
    p = attention_params(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, s, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return p, x.astype(jnp.float32), positions


@pytest.mark.parametrize("window", [None, 4])
def test_xla_and_fused_bitexact_train_mode(window):
    """ssa-xla and ssa-fused share the counter-RNG seed derivation, so the
    full attention block (proj+rope+encode included) is bit-identical."""
    cfg_x = _ssa_cfg("xla")
    cfg_f = _ssa_cfg("fused")
    key = jax.random.PRNGKey(7)
    p, x, positions = _attn_block(cfg_x, key)
    rng = jax.random.PRNGKey(3)
    out_x, _ = attention_apply(
        p, x, cfg=cfg_x, layer_window=window, positions=positions, rng=rng
    )
    out_f, _ = attention_apply(
        p, x, cfg=cfg_f, layer_window=window, positions=positions, rng=rng
    )
    np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_f))
    assert np.any(np.asarray(out_x) != 0.0)


@pytest.mark.parametrize("storage", ["dense", "packed"])
def test_xla_and_fused_bitexact_prefill_decode(storage):
    """Prefill + decode through the cache: xla vs fused backends produce
    bit-identical logits for both KV-storage layouts."""
    cfgs = [_ssa_cfg(be, storage) for be in ("xla", "fused")]
    models = [build_model(c) for c in cfgs]
    params = models[0].init(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[5, 7, 9, 11, 2]], jnp.int32)
    positions = jnp.arange(5, dtype=jnp.int32)[None]
    outs = []
    for model in models:
        cache = model.init_cache(1, 16)
        logits, cache = model.prefill(
            params, {"tokens": prompt, "positions": positions}, cache
        )
        rows = [np.asarray(logits)]
        pos = 5
        for _ in range(2):
            batch = {
                "tokens": jnp.asarray([[3]], jnp.int32),
                "positions": jnp.asarray([[pos]], jnp.int32),
            }
            logits, cache = model.decode_step(
                params, batch, cache, jnp.asarray([pos])
            )
            rows.append(np.asarray(logits))
            pos += 1
        outs.append(rows)
    for r_x, r_f in zip(*outs):
        np.testing.assert_array_equal(r_x, r_f)


def test_fused_packed_decode_bitexact_vs_fused_dense():
    """The packed decode backend (uint32 planes into the packed kernel) is
    bit-identical to fused-dense decode (re-encoded reals) — the kernel
    tile body and counter RNG are shared."""
    cfg_d = _ssa_cfg("fused", "dense")
    cfg_p = _ssa_cfg("fused", "packed")
    model_d, model_p = build_model(cfg_d), build_model(cfg_p)
    params = model_d.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    positions = jnp.arange(4, dtype=jnp.int32)[None]
    logits = []
    for model in (model_d, model_p):
        cache = model.init_cache(1, 16)
        _, cache = model.prefill(
            params, {"tokens": prompt, "positions": positions}, cache
        )
        lg, _ = model.decode_step(
            params,
            {
                "tokens": jnp.asarray([[3]], jnp.int32),
                "positions": jnp.asarray([[4]], jnp.int32),
            },
            cache,
            jnp.asarray([4]),
        )
        logits.append(np.asarray(lg))
    np.testing.assert_array_equal(logits[0], logits[1])


@pytest.mark.parametrize("storage", ["dense", "packed"])
def test_xla_and_fused_bitexact_windowed_arch(storage):
    """Sliding-window architecture (gemma2 'LG' alternation): xla vs fused
    stay bit-identical through windowed prefill+decode for both storages."""
    cfgs = [_ssa_cfg(be, storage, arch="gemma2_9b") for be in ("xla", "fused")]
    models = [build_model(c) for c in cfgs]
    params = models[0].init(jax.random.PRNGKey(1))
    prompt = jnp.asarray([[2, 4, 6, 8]], jnp.int32)
    positions = jnp.arange(4, dtype=jnp.int32)[None]
    outs = []
    for model in models:
        cache = model.init_cache(1, 24)
        logits, cache = model.prefill(
            params, {"tokens": prompt, "positions": positions}, cache
        )
        rows = [np.asarray(logits)]
        lg, _ = model.decode_step(
            params,
            {
                "tokens": jnp.asarray([[1]], jnp.int32),
                "positions": jnp.asarray([[4]], jnp.int32),
            },
            cache,
            jnp.asarray([4]),
        )
        rows.append(np.asarray(lg))
        outs.append(rows)
    for r_x, r_f in zip(*outs):
        np.testing.assert_array_equal(r_x, r_f)


def test_fused_backend_trains_at_model_level():
    cfg = _ssa_cfg("fused")
    model = build_model(cfg)
    key = jax.random.PRNGKey(5)
    params = model.init(key)
    b, s = 1, 8
    batch = {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "positions": jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
    }
    loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch, rng=key))(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert gnorm > 0


# ---------------------------------------------------------------------------
# statistical equivalence with the historical threefry reference (core.ssa)
# ---------------------------------------------------------------------------
class _ThreefrySsaBackend:
    """core.ssa (threefry-keyed uniforms) exposed as a registry backend —
    exercises register_backend overriding and provides the independent
    estimator for the rate-level test below."""

    name = "ssa-xla"

    def supports(self, a, mode):
        return a.impl == "ssa"

    def apply(self, inv: AttentionInvocation):
        from repro.core.ssa import ssa_attention

        qs, ks, vs = folded_spike_trains(inv)
        seeds = (
            inv.seeds if inv.seeds is not None
            else jnp.zeros(inv.q.shape[0], jnp.uint32)
        )
        rng = jax.random.fold_in(jax.random.PRNGKey(0), seeds[0])
        spikes = ssa_attention(rng, qs, ks, vs, causal=inv.causal, window=inv.window)
        return rate_decode(spikes, inv.q.shape[0], inv.q.shape[2])


def test_counter_rng_backend_matches_threefry_in_expectation():
    """ssa-xla (counter RNG, == ssa-fused bit-for-bit) and the historical
    core.ssa path sample the same spike distribution: Monte-Carlo means of
    the full attention block agree within CLT tolerance at model level."""
    cfg = _ssa_cfg("xla")
    key = jax.random.PRNGKey(11)
    p, x, positions = _attn_block(cfg, key, b=1, s=6)

    def one(cfg_):
        def f(rng):
            out, _ = attention_apply(
                p, x, cfg=cfg_, layer_window=None, positions=positions, rng=rng
            )
            return out

        return jax.jit(jax.vmap(f))

    n = 192
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    samples_counter = np.asarray(one(cfg)(keys))

    original = get_backend("ssa-xla")
    try:
        register_backend(_ThreefrySsaBackend())
        samples_threefry = np.asarray(one(cfg)(keys))
    finally:
        register_backend(original)

    m_c, m_t = samples_counter.mean(0), samples_threefry.mean(0)
    stderr = np.sqrt(
        samples_counter.var(0) / n + samples_threefry.var(0) / n
    )
    assert np.abs(m_c - m_t).max() < (6.0 * stderr + 1e-3).max(), (
        np.abs(m_c - m_t).max(),
        stderr.max(),
    )
    # and the two estimators genuinely differ per sample (different RNG)
    assert np.any(samples_counter != samples_threefry)


# ---------------------------------------------------------------------------
# packed fused decode: no unpack of cached planes (HLO inspection)
# ---------------------------------------------------------------------------
def _decode_lowering_text(cfg, b=2, max_seq=32):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(b, max_seq)
    batch = {
        "tokens": jnp.zeros((b, 1), jnp.int32),
        "positions": jnp.full((b, 1), 4, jnp.int32),
    }
    idx = jnp.full((b,), 4, jnp.int32)
    f = jax.jit(lambda p, bt, c, i: model.decode_step(p, bt, c, i))
    return cfg, f.lower(params, batch, cache, idx).as_text()


def test_packed_fused_decode_never_unpacks_cached_planes():
    """Acceptance check: with backend='fused' + spike_storage='packed', the
    decode step's lowering contains no dense unpacked-cache tensor — the
    uint32 planes flow straight into the packed kernel.  The xla backend
    (control) does materialise the unpacked planes."""
    b, max_seq = 2, 32
    cfg_f, text_f = _decode_lowering_text(_ssa_cfg("fused", "packed"), b, max_seq)
    a = cfg_f.attention
    t, hkv, hd = a.ssa_time_steps, a.num_kv_heads, a.head_dim
    # unpack_spikes(cache) shapes: (B, S, T, H_kv, hd) and its (T, B, S, ...)
    # transpose — neither may appear in the fused lowering
    unpacked = f"tensor<{b}x{max_seq}x{t}x{hkv}x{hd}xf32>"
    transposed = f"tensor<{t}x{b}x{max_seq}x{hkv}x{hd}xf32>"
    assert unpacked not in text_f and transposed not in text_f
    # packed words do reach the kernel: uint32 cache-plane tensors present
    assert "ui32" in text_f

    _, text_x = _decode_lowering_text(_ssa_cfg("xla", "packed"), b, max_seq)
    assert unpacked in text_x or transposed in text_x


def _pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, inside scans and jits too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


def test_packed_fused_gqa_decode_reads_each_kv_head_once():
    """Grouped-query decode (2 query heads per KV head): the packed SSA
    kernel's K/V operands keep one row per (sequence, KV head) — no plane
    is repeated to query heads — and the query words (operand 3, the one
    the benchmark's kernel reader keys on) stay uint32, grouped to match."""
    b, max_seq = 2, 32
    cfg = _ssa_cfg("fused", "packed", attention__num_kv_heads=2)
    a = cfg.attention
    assert a.num_kv_heads < a.num_heads
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jnp.zeros((b, 1), jnp.int32),
        "positions": jnp.full((b, 1), 4, jnp.int32),
    }
    jaxpr = jax.make_jaxpr(model.decode_step)(
        params, batch, model.init_cache(b, max_seq), jnp.full((b,), 4, jnp.int32)
    )
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert calls
    for eqn in calls:
        seeds, q_pos, kv_pos, q, k, v = (x.aval for x in eqn.invars)
        assert q.dtype == jnp.uint32
        assert k.shape[0] == v.shape[0] == kv_pos.shape[0] == b * a.num_kv_heads
        assert q.shape[0] == b * a.num_kv_heads
        assert seeds.shape == q_pos.shape  # one seed per query row


# ---------------------------------------------------------------------------
# serving-engine token identity across backends
# ---------------------------------------------------------------------------
def test_engines_token_identical_across_backends():
    """Acceptance check: fused-packed serving == xla serving, token for
    token, for the same seed (greedy)."""
    variants = [
        _ssa_cfg("xla", "dense"),
        _ssa_cfg("xla", "packed"),
        _ssa_cfg("fused", "packed"),
    ]
    models = [build_model(c) for c in variants]
    params = models[0].init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompts = [
        rng.integers(0, variants[0].vocab_size, int(l)).astype(np.int32)
        for l in (3, 5)
    ]
    streams = []
    for model in models:
        eng = ServingEngine(model, params, num_slots=2, max_seq=32)
        reqs = [
            Request(uid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)
        ]
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_done(max_ticks=60)
        assert len(done) == len(reqs)
        streams.append([r.out_tokens for r in reqs])
    assert streams[0] == streams[1] == streams[2], streams


# ---------------------------------------------------------------------------
# spiking ViT rides the same dispatch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "fused"])
def test_spiking_vit_backends(backend):
    cfg = with_overrides(
        get_smoke_config("spiking_vit_small"),
        attention__impl="ssa",
        attention__backend=backend,
    )
    model = build_model(cfg)
    key = jax.random.PRNGKey(4)
    params = model.init(key)
    patches = jax.random.normal(key, (2, model.num_patches, model.patch_dim))
    logits = model.forward(params, patches, key)
    assert logits.shape == (2, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_spiking_vit_xla_fused_bitexact():
    base = get_smoke_config("spiking_vit_small")
    key = jax.random.PRNGKey(6)
    outs = []
    for backend in ("xla", "fused"):
        cfg = with_overrides(
            base, attention__impl="ssa", attention__backend=backend
        )
        model = build_model(cfg)
        params = model.init(key)
        patches = jax.random.normal(key, (1, model.num_patches, model.patch_dim))
        outs.append(np.asarray(model.forward(params, patches, key)))
    np.testing.assert_array_equal(outs[0], outs[1])
