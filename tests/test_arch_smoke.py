"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, assert output shapes + finite values; plus a decode-path test per family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, ShapeConfig, get_smoke_config
from repro.configs.registry import ARCH_IDS
from repro.models import build_model

SMOKE_SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")


def _materialize_batch(model, cfg, shape, key):
    specs = model.input_specs(shape)
    batch = {}
    for name, spec in specs.items():
        key, sub = jax.random.split(key)
        if spec.dtype == jnp.int32:
            if name == "positions":
                if len(spec.shape) == 3:  # mrope (3, B, S)
                    pos = jnp.broadcast_to(
                        jnp.arange(spec.shape[-1])[None, None], spec.shape
                    ).astype(jnp.int32)
                else:
                    pos = jnp.broadcast_to(
                        jnp.arange(spec.shape[-1])[None], spec.shape
                    ).astype(jnp.int32)
                batch[name] = pos
            else:
                hi = max(cfg.vocab_size, 2)
                batch[name] = jax.random.randint(sub, spec.shape, 0, hi)
        else:
            batch[name] = jax.random.normal(sub, spec.shape, dtype=jnp.float32).astype(
                spec.dtype
            )
    if "positions" not in batch and "positions" in [n for n in specs]:
        pass
    return batch


def _ensure_positions(batch, specs_keys, b, s, mrope=False):
    if "positions" not in batch:
        shape = (3, b, s) if mrope else (b, s)
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s)[None], (b, s)
        ) if not mrope else jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s))
    return batch


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS if a != "spiking_vit_small"])
def test_arch_train_step_smoke(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    batch = _materialize_batch(model, cfg, SMOKE_SHAPE, jax.random.fold_in(key, 1))
    b, s = SMOKE_SHAPE.global_batch, SMOKE_SHAPE.seq_len
    _ensure_positions(batch, batch.keys(), b, s, cfg.attention.rope_type == "mrope")
    if "labels" not in batch:
        batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)

    loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch, rng=key))(params)
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    leaves = jax.tree.leaves(grads)
    assert leaves, f"{arch}: no grads"
    for g in leaves:
        assert np.all(np.isfinite(np.asarray(g, np.float32))), f"{arch}: non-finite grad"


@pytest.mark.parametrize(
    "arch",
    ["codeqwen15_7b", "gemma2_9b", "mixtral_8x7b", "zamba2_1_2b", "xlstm_125m",
     "whisper_small", "qwen2_vl_2b"],
)
def test_arch_prefill_then_decode(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    b, s_pre, cache_len = 2, 8, 16
    mrope = cfg.attention.rope_type == "mrope"

    cache = model.init_cache(b, cache_len)
    pre_shape = ShapeConfig("p", s_pre, b, "prefill")
    batch = _materialize_batch(model, cfg, pre_shape, key)
    _ensure_positions(batch, batch.keys(), b, s_pre, mrope)
    if "tokens" not in batch and cfg.frontend == "tokens":
        batch["tokens"] = jax.random.randint(key, (b, s_pre), 0, cfg.vocab_size)

    logits, cache = model.prefill(params, batch, cache, rng=key)
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    # two decode steps
    for step in range(2):
        pos_val = s_pre + step
        if mrope:
            positions = jnp.full((3, b, 1), pos_val, jnp.int32)
        else:
            positions = jnp.full((b, 1), pos_val, jnp.int32)
        dec_batch = {
            "positions": positions,
            "tokens": jnp.full((b, 1), 3, jnp.int32),
        }
        if cfg.frontend == "embeddings" and cfg.family != "audio":
            dec_batch["embeds"] = jnp.zeros((b, 1, cfg.d_model), jnp.dtype(cfg.dtype))
            del dec_batch["tokens"]
        logits, cache = model.decode_step(
            params, dec_batch, cache, jnp.asarray(pos_val), rng=key
        )
        assert logits.shape == (b, 1, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits, np.float32))), f"{arch} step {step}"


def test_spiking_vit_all_impls():
    import dataclasses

    base = get_smoke_config("spiking_vit_small")
    key = jax.random.PRNGKey(2)
    for impl in ("ann", "ssa", "spikformer"):
        cfg = dataclasses.replace(
            base, attention=dataclasses.replace(base.attention, impl=impl)
        )
        model = build_model(cfg)
        params = model.init(key)
        batch = {
            "patches": jax.random.normal(key, (2, model.num_patches, model.patch_dim)),
            "label": jnp.array([1, 2]),
        }
        loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch, key))(params)
        assert np.isfinite(float(loss)), impl
        gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
        assert gnorm > 0, f"{impl}: zero gradients"


def test_ssa_mode_in_lm_arch():
    """The paper's technique as a first-class LM feature: SSA attention in a
    GQA decoder trains and produces finite grads."""
    import dataclasses

    cfg = get_smoke_config("codeqwen15_7b")
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, impl="ssa", ssa_time_steps=2)
    )
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = model.init(key)
    b, s = 2, 16
    batch = {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
        "positions": jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
    }
    loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch, rng=key))(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
    assert gnorm > 0


@pytest.mark.parametrize(
    "arch,dtype",
    [("codeqwen15_7b", "float32"), ("codeqwen15_7b", "bfloat16"),
     ("mixtral_8x7b", "float32")],
)
def test_decoder_init_jit_matches_eager(arch, dtype):
    """``DecoderLM.init`` compiles one program; its draws must be the bits
    an eager, op-by-op initialisation draws (XLA may not fold the init
    scales into the sampler's constants)."""
    from repro.configs import with_overrides

    cfg = with_overrides(get_smoke_config(arch), dtype=dtype)
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    jitted = model.init(key)
    with jax.disable_jit():
        eager = model._init(key)
    assert jax.tree.structure(jitted) == jax.tree.structure(eager)
    assert jitted["embed"].dtype == jnp.dtype(dtype)
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
