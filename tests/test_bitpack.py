"""Bit-plane spike subsystem: pack/unpack round trips, popcount-matmul
equivalence (ref + Pallas interpret), packed-vs-dense fused-SSA bit-identity,
the grouped-query packed tile against the oracles, and the packed spiking KV
cache end to end."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bitpack import (
    pack_spikes,
    packed_width,
    popcount32,
    popcount_matmul_ref,
    unpack_spikes,
)
from repro.kernels.popcount_matmul import popcount_matmul
from repro.kernels.ssa_attention.ops import sdsa_attention, ssa_attention
from repro.kernels.ssa_attention.ref import sdsa_reference, ssa_reference

INTERP = True  # CPU container: Pallas kernels run in interpret mode


def _spikes(key, shape, rate=0.5, dtype=jnp.float32):
    return (jax.random.uniform(key, shape) < rate).astype(dtype)


# ---------------------------------------------------------------------------
# pack / unpack round trip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.bool_])
@pytest.mark.parametrize(
    "shape,axis",
    [
        ((7,), -1),
        ((3, 32), -1),
        ((2, 5, 33), -1),          # one pad bit short of two words
        ((4, 2, 100), -1),
        ((31, 2, 8), 0),           # fold the T time axis instead
        ((2, 70, 3), 1),
    ],
)
def test_pack_unpack_roundtrip(shape, axis, dtype):
    key = jax.random.PRNGKey(hash((shape, axis)) % (2**31))
    s = _spikes(key, shape, 0.37, dtype)
    p = pack_spikes(s, axis=axis)
    assert p.dtype == jnp.uint32
    assert p.shape[axis] == packed_width(shape[axis])
    u = unpack_spikes(p, shape[axis], axis=axis)
    assert u.shape == shape
    np.testing.assert_array_equal(
        np.asarray(u, np.float32), np.asarray(s, np.float32)
    )


def test_pack_pad_bits_are_zero():
    s = jnp.ones((2, 33), jnp.float32)
    p = pack_spikes(s)
    # word 1 holds bit 32 only; bits 33..63 must be zero-padded
    assert int(p[0, 1]) == 1


def test_popcount32_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 2**32, size=(256,), dtype=np.uint32)
    ours = np.asarray(popcount32(jnp.asarray(x)))
    theirs = np.array([bin(v).count("1") for v in x], dtype=np.uint32)
    np.testing.assert_array_equal(ours, theirs)


# ---------------------------------------------------------------------------
# popcount matmul: ref == dense einsum == Pallas kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "m,n,d",
    [(16, 16, 16), (130, 70, 100), (1, 200, 64), (257, 129, 40)],
)
def test_popcount_matmul_matches_dense_einsum(m, n, d):
    key = jax.random.PRNGKey(m * 31 + n)
    a = _spikes(key, (m, d), 0.5)
    b = _spikes(jax.random.fold_in(key, 1), (n, d), 0.5)
    ap, bp = pack_spikes(a), pack_spikes(b)
    dense = jnp.einsum("md,nd->mn", a, b).astype(jnp.int32)
    ref = popcount_matmul_ref(ap, bp)
    kern = popcount_matmul(ap, bp, interpret=INTERP)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(dense))
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(dense))


def test_popcount_matmul_batched():
    key = jax.random.PRNGKey(9)
    a = _spikes(key, (3, 50, 70), 0.3)
    b = _spikes(jax.random.fold_in(key, 1), (3, 60, 70), 0.7)
    out = popcount_matmul(pack_spikes(a), pack_spikes(b), interpret=INTERP)
    ref = jnp.einsum("bmd,bnd->bmn", a, b).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_popcount_matmul_broadcasts_batch_dims_like_ref():
    key = jax.random.PRNGKey(10)
    a = _spikes(key, (2, 8, 64), 0.5)            # batched queries
    b = _spikes(jax.random.fold_in(key, 1), (8, 64), 0.5)  # shared keys
    ap, bp = pack_spikes(a), pack_spikes(b)
    out = popcount_matmul(ap, bp, interpret=INTERP)
    ref = popcount_matmul_ref(ap, bp)
    assert out.shape == (2, 8, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# packed fused SSA == dense fused SSA (same counter-RNG seed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,n_q,n_kv,d,causal,window",
    [
        (1, 16, 16, 16, False, None),      # full mask
        (2, 128, 128, 64, True, None),     # causal
        (3, 200, 200, 48, True, 64),       # causal + sliding window
        (1, 1, 96, 32, True, None),        # decode: 1 query vs cache
        (1, 257, 129, 40, False, None),    # adversarial padding
    ],
)
def test_packed_ssa_bit_identical_to_dense(b, n_q, n_kv, d, causal, window):
    key = jax.random.PRNGKey(n_q * 13 + n_kv)
    q = _spikes(key, (b, n_q, d), 0.4)
    k = _spikes(jax.random.fold_in(key, 1), (b, n_kv, d), 0.6)
    v = _spikes(jax.random.fold_in(key, 2), (b, n_kv, d), 0.5)
    seed = jnp.uint32(1234)
    dense = ssa_attention(q, k, v, seed, causal, window, 128, 128, INTERP)
    packed = ssa_attention(
        pack_spikes(q), pack_spikes(k), pack_spikes(v), seed,
        causal, window, 128, 128, INTERP, packed=True, d_k=d,
    )
    np.testing.assert_array_equal(
        np.asarray(dense, np.float32), np.asarray(packed, np.float32)
    )


# ---------------------------------------------------------------------------
# grouped packed tile: B_q = B_kv x groups query rows against KV-head planes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_kv", [96, 300, 2048])
@pytest.mark.parametrize("n_q", [1, 4, 16])
@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("kernel", ["ssa", "sdsa"])
def test_packed_grouped_bit_identical_to_reference(kernel, groups, n_q, n_kv):
    """A packed call whose K/V planes hold one row per KV head (the query
    heads of a group tiled together) samples exactly what the oracle
    samples on K/V trains repeated to every query head: per-row seeds of a
    head slice (head0 != 0), two sequences of different lengths, holes."""
    from repro.attention.base import derive_step_row_seeds

    b, h_kv, d = 2, 2, 40
    h = h_kv * groups
    key = jax.random.PRNGKey(groups * 1000 + n_q * 10 + n_kv)
    q = _spikes(key, (b * h, n_q, d), 0.4)
    k = _spikes(jax.random.fold_in(key, 1), (b * h_kv, n_kv, d), 0.6)
    v = _spikes(jax.random.fold_in(key, 2), (b * h_kv, n_kv, d), 0.5)
    # sequence 0 fills the cache, sequence 1 stops 40 rows short; both have
    # never-written rows (-1) inside the span
    lengths = np.array([n_kv, n_kv - 40])
    kv_seq = np.where(
        np.arange(n_kv)[None] < lengths[:, None], np.arange(n_kv)[None], -1
    )
    kv_seq[:, 3:7] = -1
    q_seq = lengths[:, None] - n_q + np.arange(n_q)[None]
    kv_pos = jnp.asarray(np.repeat(kv_seq, h_kv, axis=0), jnp.int32)
    q_pos = jnp.asarray(np.repeat(q_seq, h, axis=0), jnp.int32)
    seeds = derive_step_row_seeds(
        jnp.asarray([0x1234, 0xBEEF], jnp.uint32), 2, h, head0=3
    )[1]
    fused, ref = {
        "ssa": (functools.partial(ssa_attention, packed=True), ssa_reference),
        "sdsa": (sdsa_attention, sdsa_reference),
    }[kernel]
    out = fused(
        pack_spikes(q), pack_spikes(k), pack_spikes(v), seeds, True, None,
        128, 128, INTERP, q_positions=q_pos, kv_positions=kv_pos, d_k=d,
    )
    want = ref(
        q, jnp.repeat(k, groups, axis=0), jnp.repeat(v, groups, axis=0),
        seeds, causal=True, q_positions=q_pos,
        kv_positions=jnp.repeat(kv_pos, groups, axis=0),
    )
    assert out.shape == (b * h, n_q, d)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(want, np.float32)
    )
    assert 0 < float(np.asarray(want).mean()) < 1


def test_packed_ssa_rejects_bad_inputs():
    q = jnp.zeros((1, 8, 2), jnp.uint32)
    with pytest.raises(ValueError):
        ssa_attention(q, q, q, jnp.uint32(0), packed=True)  # missing d_k
    with pytest.raises(ValueError):
        ssa_attention(q, q, q, jnp.uint32(0), packed=True, d_k=128)  # W mismatch
    qf = jnp.zeros((1, 8, 2), jnp.float32)
    with pytest.raises(TypeError):
        ssa_attention(qf, qf, qf, jnp.uint32(0), packed=True, d_k=64)
    # k/v are validated too, not just q
    k_narrow = jnp.zeros((1, 8, 1), jnp.uint32)
    with pytest.raises(ValueError):
        ssa_attention(q, k_narrow, q, jnp.uint32(0), packed=True, d_k=64)
    with pytest.raises(TypeError):
        ssa_attention(q, q, qf, jnp.uint32(0), packed=True, d_k=64)
    # K/V rows must divide the query rows (one per KV head) and match
    q3 = jnp.zeros((3, 8, 2), jnp.uint32)
    k2 = jnp.zeros((2, 8, 2), jnp.uint32)
    with pytest.raises(ValueError):
        ssa_attention(q3, k2, k2, jnp.uint32(0), packed=True, d_k=64)
    with pytest.raises(ValueError):
        ssa_attention(q, q, k2, jnp.uint32(0), packed=True, d_k=64)


# ---------------------------------------------------------------------------
# packed spiking KV cache: model-level decode bit-identity + footprint
# ---------------------------------------------------------------------------
def _ssa_cfgs(arch="codeqwen15_7b"):
    from repro.configs import get_smoke_config, with_overrides

    dense = with_overrides(get_smoke_config(arch), attention__impl="ssa")
    packed = with_overrides(dense, attention__spike_storage="packed")
    return dense, packed


def test_packed_cache_decode_matches_dense():
    from repro.models import build_model

    cfg_d, cfg_p = _ssa_cfgs()
    model_d, model_p = build_model(cfg_d), build_model(cfg_p)
    params = model_d.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[5, 7, 9, 11]], jnp.int32)
    positions = jnp.arange(4, dtype=jnp.int32)[None]

    outs = []
    for model in (model_d, model_p):
        cache = model.init_cache(1, 24)
        logits, cache = model.prefill(
            params, {"tokens": prompt, "positions": positions}, cache
        )
        toks = [int(jnp.argmax(logits[0, -1]))]
        pos = 4
        for _ in range(4):
            batch = {
                "tokens": jnp.asarray([[toks[-1]]], jnp.int32),
                "positions": jnp.asarray([[pos]], jnp.int32),
            }
            logits, cache = model.decode_step(
                params, batch, cache, jnp.asarray([pos])
            )
            toks.append(int(jnp.argmax(logits[0, -1])))
            pos += 1
        outs.append(toks)
    assert outs[0] == outs[1], outs


def test_packed_cache_is_smaller_and_uint32():
    from repro.models import build_model

    cfg_d, cfg_p = _ssa_cfgs()
    cache_d = build_model(cfg_d).init_cache(2, 32)
    cache_p = build_model(cfg_p).init_cache(2, 32)
    nb_d = sum(int(l.nbytes) for l in jax.tree.leaves(cache_d))
    nb_p = sum(int(l.nbytes) for l in jax.tree.leaves(cache_p))
    assert nb_p < nb_d / 4
    leaves = {k for slot in cache_p for k in slot}
    assert leaves == {"ks", "vs", "pos"}
    assert all(
        slot["ks"].dtype == jnp.uint32 and slot["vs"].dtype == jnp.uint32
        for slot in cache_p
    )


def test_packed_storage_requires_ssa_impl():
    from repro.models import build_model

    cfg_d, _ = _ssa_cfgs()
    bad = dataclasses.replace(
        cfg_d,
        attention=dataclasses.replace(
            cfg_d.attention, impl="ann", spike_storage="packed"
        ),
    )
    with pytest.raises(ValueError):
        build_model(bad)


def test_packed_storage_requires_decoder_lm_family():
    """Families whose cache_specs never build packed leaves must be refused,
    not silently handed a dense cache."""
    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model

    cfg = with_overrides(
        get_smoke_config("zamba2_1_2b"),
        attention__impl="ssa",
        attention__spike_storage="packed",
    )
    with pytest.raises(ValueError):
        build_model(cfg)


def test_kv_traffic_model_claim():
    """Acceptance: >= 8x modeled KV bytes moved per decode step, D_K >= 64."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parents[1]))
    from benchmarks.energy_model import storage_comparison

    rows = storage_comparison(n_ctx=4096, n_kv_heads=8, t=4, d_ks=(64, 128))
    for d_k, r in rows.items():
        assert r["moved_ratio"] >= 8.0, (d_k, r)
