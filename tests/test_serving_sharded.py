"""Sharded + replicated serving: bit-identity against the committed golden
fixtures, dispatch invariants, and per-shard accounting.

Tensor-parallel tests need a multi-device host (the CI ``serving-sharded``
lane forces 8 CPU devices via ``XLA_FLAGS``); they skip cleanly on the
single-device tier-1 runner.  The replica layer is pure host-side dispatch
over ordinary engines, so every replica test runs on one device — the
tier-1 lane covers it.  ``test_sharded_identity_subprocess`` additionally
probes the full TP matrix from a single-device pytest process through the
``test_distributed_lowering.py`` subprocess pattern (slow lane).
"""
import dataclasses
import functools
import json
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.obs import Tracer
from repro.serving import ReplicatedEngine, Request, ServingEngine

from conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()

_GOLDEN = Path(__file__).parent / "golden"

# the ISSUE-9 identity matrix: every spiking-relevant golden fixture
# (ann / ssa-dense / ssa-packed x slab / paged, windowed gemma2 included)
MATRIX = [
    ("codeqwen-ssa-dense-slab", "codeqwen15_7b", "ssa", "dense", "slab"),
    ("codeqwen-ssa-dense-paged", "codeqwen15_7b", "ssa", "dense", "paged"),
    ("codeqwen-ssa-packed-slab", "codeqwen15_7b", "ssa", "packed", "slab"),
    ("codeqwen-ssa-packed-paged", "codeqwen15_7b", "ssa", "packed", "paged"),
    ("gemma2-ssa-packed-paged", "gemma2_9b", "ssa", "packed", "paged"),
    ("codeqwen-ann-dense-slab", "codeqwen15_7b", "ann", "dense", "slab"),
    ("codeqwen-ann-dense-paged", "codeqwen15_7b", "ann", "dense", "paged"),
]

PROMPTS = ([3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8])
SEEDS = (17, 23)
MAX_NEW = 5


@functools.lru_cache(maxsize=None)
def _model_and_params(arch, impl, storage, layout):
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(
        cfg,
        attention=dataclasses.replace(
            cfg.attention, impl=impl, spike_storage=storage,
            cache_layout=layout,
        ),
    )
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _pinned_requests():
    return [
        Request(uid=i, prompt=np.asarray(p, np.int32),
                max_new_tokens=MAX_NEW, seed=s)
        for i, (p, s) in enumerate(zip(PROMPTS, SEEDS))
    ]


def _streams(engine):
    reqs = _pinned_requests()
    for r in reqs:
        engine.submit(r)
    done = engine.run_until_done(max_ticks=100)
    assert len(done) == len(reqs)
    return [list(map(int, r.out_tokens)) for r in reqs]


def _golden_streams(name: str):
    with open(_GOLDEN / f"{name}.json") as f:
        payload = json.load(f)
    assert payload["prompts"] == [list(p) for p in PROMPTS]
    assert payload["seeds"] == list(SEEDS)
    return payload["streams"]


# ---------------------------------------------------------------------------
# tensor parallelism: bit-identical to the committed single-device fixtures
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("name,arch,impl,storage,layout", MATRIX,
                         ids=[m[0] for m in MATRIX])
def test_sharded_streams_match_golden(name, arch, impl, storage, layout,
                                      shards):
    if len(jax.devices()) < shards:
        pytest.skip(f"needs >= {shards} devices")
    _, model, params = _model_and_params(arch, impl, storage, layout)
    kw = {"page_size": 8} if layout == "paged" else {}
    eng = ServingEngine(model, params, num_slots=2, max_seq=32,
                        mesh_shards=shards, **kw)
    assert _streams(eng) == _golden_streams(name)


@pytest.mark.parametrize("shards", [2])
def test_sharded_engine_accounting(shards):
    if len(jax.devices()) < shards:
        pytest.skip(f"needs >= {shards} devices")
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged")
    plain = ServingEngine(model, params, num_slots=2, max_seq=32,
                          page_size=8)
    tracer = Tracer()
    eng = ServingEngine(model, params, num_slots=2, max_seq=32, page_size=8,
                        mesh_shards=shards, tracer=tracer)
    # logical bytes are sharding-invariant; the per-shard view splits the
    # head-sharded payload leaves and replicates the bookkeeping ones
    assert eng.kv_cache_nbytes() == plain.kv_cache_nbytes()
    per = eng.kv_shard_nbytes()
    assert len(per) == shards
    assert all(b == per[0] for b in per)
    assert per[0] < eng.kv_cache_nbytes()
    stats = eng.stats()
    assert stats["mesh_shards"] == shards
    assert stats["kv_shard_nbytes"] == per
    _streams(eng)
    # every emitted event is tagged with the shard count
    events = list(tracer.events())
    assert events
    assert all(ev.data.get("shards") == shards for ev in events)


def test_mesh_shards_requires_devices():
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged")
    toomany = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="mesh_shards"):
        ServingEngine(model, params, num_slots=2, max_seq=32, page_size=8,
                      mesh_shards=toomany)


def test_plain_engine_events_untagged():
    """Sharding off => event payloads carry no shard/replica fields, so
    the committed golden event-stream signatures stay byte-identical."""
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged")
    tracer = Tracer()
    eng = ServingEngine(model, params, num_slots=2, max_seq=32, page_size=8,
                        tracer=tracer)
    _streams(eng)
    events = list(tracer.events())
    assert events
    assert all(
        "shards" not in ev.data and "replica" not in ev.data
        for ev in events
    )


# ---------------------------------------------------------------------------
# data-parallel replicas (host-side dispatch; single-device, tier-1 lane)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,arch,impl,storage,layout", MATRIX[:4],
                         ids=[m[0] for m in MATRIX[:4]])
def test_replicated_streams_match_golden(name, arch, impl, storage, layout):
    _, model, params = _model_and_params(arch, impl, storage, layout)
    kw = {"page_size": 8} if layout == "paged" else {}
    eng = ReplicatedEngine(model, params, replicas=2, num_slots=2,
                           max_seq=32, **kw)
    assert _streams(eng) == _golden_streams(name)
    # two pinned requests over an idle two-replica engine: least-loaded
    # dispatch splits them one per replica
    assert eng.request_counts() == [1, 1]
    assert eng.owner_of(0) == 0 and eng.owner_of(1) == 1


def test_replica_events_tagged_with_replica():
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged")
    tracer = Tracer()
    eng = ReplicatedEngine(model, params, replicas=2, num_slots=2,
                           max_seq=32, page_size=8, tracer=tracer)
    _streams(eng)
    replicas = {ev.data.get("replica") for ev in tracer.events()}
    assert replicas == {0, 1}


def test_replicated_rejects_duplicate_uids():
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged")
    eng = ReplicatedEngine(model, params, replicas=2, num_slots=2,
                           max_seq=32, page_size=8)
    eng.submit(Request(uid=7, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2))
    eng.step()
    eng.submit(Request(uid=7, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2))
    with pytest.raises(ValueError, match="uid 7"):
        eng.run_until_done()


def test_prefix_affinity_routes_to_warm_replica():
    """A second wave sharing wave 1's prompt must land on the replica whose
    prefix cache already holds the pages — not on the emptier one."""
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged")
    from repro.attention import NUM_RESERVED_PAGES

    eng = ReplicatedEngine(
        model, params, replicas=2, num_slots=2, max_seq=32, page_size=8,
        num_pages=NUM_RESERVED_PAGES + 8, share_prefix=True,
        prefix_cache_pages=4,
    )
    prompt = np.arange(16, dtype=np.int32)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=2, seed=5))
    eng.run_until_done(max_ticks=50)
    warm = eng.owner_of(0)
    assert eng.engines[warm].pool.num_cached >= 1
    # the warm replica now has MORE load history but the affinity term wins
    eng.submit(Request(uid=1, prompt=prompt, max_new_tokens=2, seed=5))
    eng.run_until_done(max_ticks=50)
    assert eng.owner_of(1) == warm
    assert eng.engines[warm].stats()["cache_hits"] >= 1


# ---------------------------------------------------------------------------
# replica scheduler invariants (the fuzz contract, extended per ISSUE 9)
# ---------------------------------------------------------------------------
_MONOTONE = ("ticks", "requests_submitted", "requests_finished",
             "tokens_sampled", "queue_wait_ticks", "preemptions", "resumes",
             "pages_granted", "pages_released", "pages_retired")


def _check_replica_invariants(eng: ReplicatedEngine, prev: list[dict]):
    # no request served by two replicas: in-flight uid sets are disjoint
    # and consistent with the dispatch ledger
    seen: Counter = Counter()
    for i, e in enumerate(eng.engines):
        uids = {r.uid for r in e.queue} | {r.uid for r in e.active.values()}
        if e.paged:
            uids |= {r.uid for r in e._preempted}
            if e._inflight is not None:
                uids.add(e._inflight.req.uid)
        for uid in uids:
            seen[uid] += 1
            assert eng.owner_of(uid) == i, (uid, i)
    assert all(c == 1 for c in seen.values()), seen
    stats = []
    for i, e in enumerate(eng.engines):
        # per-replica page conservation (the pool's own books must close
        # independently of the other replicas)
        if e.paged:
            refs = e.tables.reference_counts()
            if e._inflight is not None:
                refs.update(e._inflight.pages)
            assert dict(refs) == e.pool.refcounts(), i
            assert (e.pool.num_free + len(e.pool.refcounts())
                    + e.pool.num_cached == e.pool.num_usable), i
        # per-replica counters only move forward
        s = e.stats()
        for key in _MONOTONE:
            assert s.get(key, 0) >= prev[i].get(key, 0), (i, key)
        stats.append(s)
    return stats


def _run_replica_scenario(*, replicas, lengths, arrivals, max_new, usable,
                          slots, share=False, cache=0, prefix_len=0,
                          rng_seed=0):
    from repro.attention import NUM_RESERVED_PAGES

    cfg, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged")
    rng = np.random.default_rng(rng_seed)
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    reqs = []
    for uid, (l, mn) in enumerate(zip(lengths, max_new)):
        tail = rng.integers(0, cfg.vocab_size, int(l)).astype(np.int32)
        reqs.append(Request(
            uid=uid, prompt=np.concatenate([prefix, tail])[:28],
            max_new_tokens=int(mn),
        ))
    order = np.argsort(arrivals, kind="stable")
    eng = ReplicatedEngine(
        model, params, replicas=replicas, num_slots=slots, max_seq=32,
        page_size=8, num_pages=NUM_RESERVED_PAGES + usable,
        share_prefix=share, prefix_cache_pages=cache,
    )
    done, tick, i = [], 0, 0
    prev = [{} for _ in range(replicas)]
    while i < len(order) or eng.has_pending_work:
        while i < len(order) and arrivals[order[i]] <= tick:
            eng.submit(reqs[order[i]])
            i += 1
        done.extend(eng.step())
        prev = _check_replica_invariants(eng, prev)
        tick += 1
        assert tick < 500, "replicated engine failed to drain"
    assert len(done) == len(reqs) and all(r.done for r in reqs)
    assert all(len(r.out_tokens) >= 1 for r in reqs)
    assert sum(eng.request_counts()) == len(reqs)
    assert eng.max_concurrency_seen >= 1
    for e in eng.engines:
        assert e.pool.num_used == 0
        assert not e.tables.pages and e._inflight is None
    return eng


def test_replica_invariants_fixed():
    """Tight per-replica pools under staggered arrivals: dispatch spreads
    the load, both pools cycle through pressure, books stay closed."""
    eng = _run_replica_scenario(
        replicas=2, lengths=[8, 12, 6, 10, 8], arrivals=[0, 0, 1, 2, 3],
        max_new=[8, 6, 10, 6, 8], usable=5, slots=2, rng_seed=3,
    )
    assert all(n >= 1 for n in eng.request_counts())


def test_replica_invariants_with_sharing_fixed():
    eng = _run_replica_scenario(
        replicas=2, lengths=[0, 0, 0, 0], arrivals=[0, 0, 8, 8],
        max_new=[8, 8, 8, 8], usable=6, slots=2,
        share=True, cache=3, prefix_len=16, rng_seed=5,
    )
    assert sum(e.stats().get("cache_inserts", 0)
               + e.stats()["shared_page_hits"] for e in eng.engines) >= 1


@given(data=st.data())
@settings(max_examples=4, deadline=None, derandomize=True)
def test_replica_invariants_hold_under_random_schedules(data):
    n_req = data.draw(st.integers(2, 6), label="n_req")
    _run_replica_scenario(
        replicas=data.draw(st.integers(2, 3), label="replicas"),
        lengths=[data.draw(st.integers(2, 18), label=f"len{i}")
                 for i in range(n_req)],
        arrivals=[data.draw(st.integers(0, 6), label=f"tick{i}")
                  for i in range(n_req)],
        max_new=[data.draw(st.integers(1, 8), label=f"new{i}")
                 for i in range(n_req)],
        usable=data.draw(st.integers(4, 9), label="usable"),
        slots=data.draw(st.integers(1, 2), label="slots"),
        share=data.draw(st.booleans(), label="share"),
        cache=data.draw(st.sampled_from([0, 3]), label="cache"),
        prefix_len=data.draw(st.sampled_from([0, 8]), label="prefix"),
        rng_seed=data.draw(st.integers(0, 2**16), label="rng"),
    )


# ---------------------------------------------------------------------------
# full TP matrix from a single-device pytest process (subprocess pattern)
# ---------------------------------------------------------------------------
def _run_sharded_probe(*args):
    probe = Path(__file__).parent / "_sharded_probe.py"
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(Path(__file__).parents[1] / "src"),
    }
    r = subprocess.run(
        [sys.executable, str(probe), *args],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_PROBE_OK" in r.stdout


@pytest.mark.slow
def test_sharded_identity_subprocess():
    _run_sharded_probe()


@pytest.mark.slow
def test_sharded_matches_unsharded_subprocess():
    """2- and 4-shard engines give the unsharded engine's streams, with the
    Pallas kernels (interpret mode here) run per head shard."""
    _run_sharded_probe("--vs-unsharded")


@pytest.mark.slow
def test_sharded_moe_matches_unsharded_subprocess():
    """An expert model (Mellum smoke: dropless top-k experts, window and
    full layers) under 2- and 4-shard serving gives the unsharded
    engine's streams: serving TP replicates the expert weights, so the
    grouped-matmul kernel runs whole on every shard."""
    _run_sharded_probe("--moe")
