"""Mosaic compiles of the serving path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each kernel is lowered with
``interpret=False`` and compiled for one chip of a *described* ``v5e:2x2``
topology, at the widths the served models use (codeqwen1.5-7b head_dim
128, the spiking ViT's head_dim 48).  A kernel Mosaic refuses (an
unsupported cast, a misaligned block, too much VMEM) fails here instead of
on the first chip run.  The topology is described inside a fixture, never
at import, so every test worker collects the same tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.popcount_matmul.ops import popcount_matmul
from repro.kernels.ssa_attention.ops import sdsa_attention, ssa_attention

# codeqwen1.5-7b attention: head_dim 128 -> 4 packed words; a 2048-row cache
HD, WORDS, N_KV, ROWS = 128, 4, 2048, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_ssa_dense_prefill_compiles(one_chip):
    _compile(
        lambda q, k, v, s: ssa_attention(q, k, v, s, causal=True),
        one_chip,
        *[((ROWS, N_KV, HD), jnp.bfloat16)] * 3,
        ((ROWS,), jnp.uint32),
    )


def test_ssa_dense_vit_head_dim_compiles(one_chip):
    # spiking-vit-small: 64 event tokens, head_dim 48, bidirectional
    _compile(
        lambda q, k, v, s: ssa_attention(q, k, v, s, causal=False),
        one_chip,
        *[((ROWS, 64, 48), jnp.float32)] * 3,
        ((ROWS,), jnp.uint32),
    )


@pytest.mark.parametrize("kernel", ["ssa", "sdsa"])
def test_packed_decode_compiles(one_chip, kernel):
    # one query token against a 2048-row cache of packed words
    if kernel == "ssa":
        def fn(q, k, v, s):
            return ssa_attention(q, k, v, s, causal=True, packed=True, d_k=HD)
    else:
        def fn(q, k, v, s):
            return sdsa_attention(q, k, v, s, causal=True, d_k=HD)
    _compile(
        fn,
        one_chip,
        ((ROWS, 1, WORDS), jnp.uint32),
        ((ROWS, N_KV, WORDS), jnp.uint32),
        ((ROWS, N_KV, WORDS), jnp.uint32),
        ((ROWS,), jnp.uint32),
    )


@pytest.mark.parametrize("n_q", [1, 16], ids=["decode", "chunk"])
@pytest.mark.parametrize("kernel", ["ssa", "sdsa"])
def test_packed_grouped_compiles(one_chip, kernel, n_q):
    # codeqwen1.5-7b serving: 8 requests x 32 query heads over 4 KV heads;
    # a KV head's 8 query heads share a tile: 8 rows for a decode token,
    # 128 for a 16-token prefill chunk
    b, h, h_kv = 8, 32, 4
    attend = {
        "ssa": functools.partial(ssa_attention, packed=True),
        "sdsa": sdsa_attention,
    }[kernel]

    def fn(q, k, v, s, q_pos, kv_pos):
        return attend(q, k, v, s, causal=True, q_positions=q_pos,
                      kv_positions=kv_pos, d_k=HD)

    text = _compile(
        fn,
        one_chip,
        ((b * h, n_q, WORDS), jnp.uint32),
        ((b * h_kv, N_KV, WORDS), jnp.uint32),
        ((b * h_kv, N_KV, WORDS), jnp.uint32),
        ((b * h,), jnp.uint32),
        ((b * h, n_q), jnp.int32),
        ((b * h_kv, N_KV), jnp.int32),
    )
    # one grid row per KV head, its group's rows unpadded in the tile
    assert f"f32[{b * h_kv},{h // h_kv * n_q},{HD}]" in text


@pytest.mark.parametrize(
    "m,n,w", [(N_KV, N_KV, WORDS), (300, 260, 200)], ids=["head", "wide"]
)
def test_popcount_matmul_compiles(one_chip, m, n, w):
    _compile(
        popcount_matmul,
        one_chip,
        ((m, w), jnp.uint32),
        ((n, w), jnp.uint32),
    )


@pytest.mark.parametrize("n_q", [1, 16], ids=["decode", "chunk"])
def test_packed_window_ring_compiles(one_chip, n_q):
    # mellum2 window layers: 8 requests x 32 query heads over 4 KV heads
    # against a 1040-row ring (window 1024 + one 16-row page)
    from repro.attention import window_ring

    b, h, h_kv, ring = 8, 32, 4, window_ring(1024, 16)

    def fn(q, k, v, s, q_pos, kv_pos):
        return ssa_attention(q, k, v, s, causal=True, window=1024, packed=True,
                             q_positions=q_pos, kv_positions=kv_pos, d_k=HD)

    _compile(
        fn,
        one_chip,
        ((b * h, n_q, WORDS), jnp.uint32),
        ((b * h_kv, ring, WORDS), jnp.uint32),
        ((b * h_kv, ring, WORDS), jnp.uint32),
        ((b * h,), jnp.uint32),
        ((b * h, n_q), jnp.int32),
        ((b * h_kv, ring), jnp.int32),
    )


@pytest.mark.parametrize("pairs", [64, 128], ids=["decode", "chunk"])
def test_moe_gmm_compiles(one_chip, pairs):
    # mellum2 expert layer: 64 experts of width 896 over d_model 2304; a
    # decode tick routes 8 rows x top-8 pairs, a 16-token chunk 16 x 8
    from repro.kernels.moe_gmm import moe_gmm

    d, f, e = 2304, 896, 64

    def fn(x, wg, wo, sizes):
        return moe_gmm(moe_gmm(x, wg, sizes), wo, sizes)

    text = _compile(
        fn,
        one_chip,
        ((pairs, d), jnp.bfloat16),
        ((e, d, f), jnp.bfloat16),
        ((e, f, d), jnp.bfloat16),
        ((e,), jnp.int32),
    )
    assert "moe_gmm" in text


def test_moe_experts_compile_under_serving_tp(one_chip, monkeypatch):
    # mellum2 expert layer at a decode tick under four-shard serving tensor
    # parallelism: Mosaic kernels cannot be partitioned by XLA, so the
    # grouped FFN has to run inside shard_map, whole on every shard
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro.attention
    from repro.configs.base import MoEConfig
    from repro.distributed.sharding import ServingTPRules, reset_rules, use_rules
    from repro.models.blocks import moe_dropless

    monkeypatch.setattr(repro.attention, "default_interpret", lambda: False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    rules = ServingTPRules(mesh)
    d, f, e = 2304, 896, 64
    moe = MoEConfig(e, 8, f)

    def fn(x, router, wi, wg, wo, valid):
        tok = use_rules(rules)
        try:
            p = {"router": router, "wi": wi, "wg": wg, "wo": wo}
            return moe_dropless(p, x, moe, valid)[0]
        finally:
            reset_rules(tok)

    text = _compile(
        fn,
        NamedSharding(mesh, P()),
        ((8, 1, d), jnp.bfloat16),
        ((d, e), jnp.float32),
        ((e, d, f), jnp.bfloat16),
        ((e, d, f), jnp.bfloat16),
        ((e, f, d), jnp.bfloat16),
        ((8, 1), jnp.bool_),
    )
    assert "moe_gmm" in text
