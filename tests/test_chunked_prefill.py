"""Chunked paged prefill (prefix-extend straight into pages).

The contract under test: splitting a prompt into page-aligned chunks that
prefill *directly into pool pages* (no slab-row staging, no scatter copy)
emits token streams bit-identical to the one-shot bucketed prefill — for
every backend and storage mode, at every chunk-boundary edge case, and
across pause / abort / retry of an admission mid-prefill.  Plus the
admission-granularity win (a prompt can start prefilling before its full
page grant exists) and the memory property (the chunk call's HLO holds no
O(max_prompt) staging tensor).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import NUM_RESERVED_PAGES
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.obs import Tracer
from repro.serving import Request, ServingEngine


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


def _prompts(vocab, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(l)).astype(np.int32) for l in lengths]


def _serve(arch="codeqwen15_7b", impl="ssa", storage="dense", layout="paged",
           *, prompts, slots=2, max_seq=32, max_new=6, arrivals=None,
           **engine_kw):
    cfg, model, params = _model_and_params(arch, impl, storage, layout)
    eng = ServingEngine(
        model, params, num_slots=slots, max_seq=max_seq, **engine_kw
    )
    reqs = [
        Request(uid=i, prompt=p, max_new_tokens=max_new)
        for i, p in enumerate(prompts)
    ]
    if arrivals is None:
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_done(max_ticks=400)
    else:
        done = []
        pending = sorted(zip(arrivals, reqs), key=lambda t: t[0])
        tick = 0
        while pending or eng.has_pending_work:
            while pending and pending[0][0] <= tick:
                eng.submit(pending.pop(0)[1])
            done.extend(eng.step())
            tick += 1
            assert tick < 400, "engine failed to drain"
    assert len(done) == len(reqs)
    return [r.out_tokens for r in reqs], eng


# ---------------------------------------------------------------------------
# construction / validation
# ---------------------------------------------------------------------------
def test_prefill_chunk_requires_paged_layout():
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "dense", "slab"
    )
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(model, params, num_slots=1, max_seq=32,
                      prefill_chunk=8)


def test_prefill_chunk_must_be_page_aligned():
    _, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "dense", "paged"
    )
    with pytest.raises(ValueError, match="page-aligned"):
        ServingEngine(model, params, num_slots=1, max_seq=32,
                      page_size=8, prefill_chunk=12)
    with pytest.raises(ValueError, match=">= 0"):
        ServingEngine(model, params, num_slots=1, max_seq=32,
                      page_size=8, prefill_chunk=-8)


def test_paged_engine_chunks_by_default_and_zero_disables():
    prompts = _prompts(256, [9])
    s_chunk, eng = _serve(prompts=prompts, page_size=8)
    assert eng.prefill_chunk == 8 and eng.stats()["chunked_prefills"] == 1
    s_off, eng_off = _serve(prompts=prompts, page_size=8, prefill_chunk=0)
    assert eng_off.stats()["chunked_prefills"] == 0
    assert s_chunk == s_off


def test_chunk_wider_than_window_ring_spare_rows_is_refused():
    """A window layer's ring leaves one page (+1) of spare rows (smoke
    gemma2: window 16, page 8 -> ring 24, 9 spare), so a two-page chunk
    would evict keys its own queries still see: the engine refuses it
    instead of switching such prompts to another path.  Where the ring
    holds all of ``max_seq`` nothing wraps, and any chunk is accepted."""
    _, model, params = _model_and_params("gemma2_9b", "ssa", "packed", "paged")
    with pytest.raises(ValueError, match="spare rows"):
        ServingEngine(model, params, num_slots=2, max_seq=32, page_size=8,
                      prefill_chunk=16)
    eng = ServingEngine(model, params, num_slots=2, max_seq=24, page_size=8,
                        prefill_chunk=16)
    assert eng.prefill_chunk == 16


# ---------------------------------------------------------------------------
# chunk-boundary edge cases: bit-identity with the unchunked engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prompt_len", [7, 8, 9, 3, 16, 17, 32])
@pytest.mark.parametrize("storage", ["dense", "packed"])
def test_chunk_boundary_lengths_are_bit_identical(prompt_len, storage):
    """prompt == chunk, chunk +- 1, shorter than one chunk, == max_seq,
    and a one-past-power-of-two length — all must stream exactly what the
    unchunked (one-shot slab-staged) engine streams."""
    prompts = _prompts(256, [prompt_len], seed=prompt_len)
    kw = dict(storage=storage, prompts=prompts, slots=1, max_seq=32,
              page_size=8)
    s_off, _ = _serve(prefill_chunk=0, **kw)
    s_chunk, eng = _serve(prefill_chunk=8, **kw)
    assert s_chunk == s_off
    assert eng.stats()["prefill_chunks_run"] == -(-prompt_len // 8)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunk_size_is_a_pure_performance_knob(chunk):
    """Any page-aligned chunk size yields the same streams (draws are
    position-keyed, never chunk-keyed)."""
    prompts = _prompts(256, [5, 11, 17], seed=2)
    kw = dict(prompts=prompts, slots=2, max_seq=32, page_size=8)
    s_ref, _ = _serve(prefill_chunk=0, **kw)
    s_chunk, _ = _serve(prefill_chunk=chunk, **kw)
    assert s_chunk == s_ref


def test_chunk_smaller_than_sliding_window_matches_gemma2():
    """gemma2's window (16 in the smoke config) spans two 8-token chunks:
    the second chunk's queries must attend across the chunk boundary into
    the first chunk's pages, through the rolling-window mask."""
    prompts = _prompts(256, [13, 10], seed=3)
    kw = dict(arch="gemma2_9b", storage="packed", prompts=prompts, slots=2,
              max_seq=32, page_size=8, max_new=8)
    s_off, _ = _serve(prefill_chunk=0, **kw)
    s_chunk, eng = _serve(prefill_chunk=8, **kw)
    assert s_chunk == s_off
    assert eng.stats()["chunked_prefills"] == 2


def test_overlong_and_overwindow_prompts_fall_back_to_one_shot():
    """Only prompts longer than ``max_seq`` tail-keep in the slab staging
    row — a layout chunk writes cannot reproduce — so only they keep the
    one-shot path.  A prompt longer than the sliding window (17 > smoke
    gemma2 window 16) now chunks across it: the window layers' ring holds
    the window plus one page, and matches the slab engine; the overlong
    prompt streams what the one-shot paged engine streams."""
    prompts = _prompts(256, [17, 5], seed=4)  # 17 > smoke gemma2 window 16
    kw = dict(arch="gemma2_9b", storage="packed", slots=2, max_seq=32,
              page_size=8)
    s_slab, _ = _serve(arch="gemma2_9b", storage="packed", layout="slab",
                       prompts=prompts, slots=2, max_seq=32)
    s_chunk, eng = _serve(prompts=prompts, **kw)
    assert s_chunk == s_slab
    assert eng.stats()["chunked_prefills"] == 2
    overlong = _prompts(256, [37], seed=4)  # 37 > max_seq 32
    s_long, eng = _serve(prompts=overlong, **kw)
    s_one_shot, _ = _serve(prompts=overlong, prefill_chunk=0, **kw)
    assert s_long == s_one_shot
    assert eng.stats()["chunked_prefills"] == 0


# ---------------------------------------------------------------------------
# admission granularity: pages claimed per chunk, pause / abort mid-prefill
# ---------------------------------------------------------------------------
def _drive(eng, reqs, arrivals, probe=None, max_ticks=400):
    done, tick, i = [], 0, 0
    while i < len(reqs) or eng.has_pending_work:
        while i < len(reqs) and arrivals[i] <= tick:
            eng.submit(reqs[i])
            i += 1
        done.extend(eng.step())
        if probe is not None:
            probe(eng)
        tick += 1
        assert tick < max_ticks, "engine failed to drain"
    return done


def test_admission_starts_before_full_page_grant():
    """Acceptance: a prompt needing more pages than are ever simultaneously
    free while an earlier request runs is admitted anyway — prefill pauses
    at a chunk boundary and resumes as pages free — and its stream is
    bit-identical to a fresh single-request engine."""
    cfg, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged"
    )
    long_prompt = _prompts(cfg.vocab_size, [28], seed=5)[0]
    short = _prompts(cfg.vocab_size, [8], seed=6)[0]

    def tight_engine(**kw):
        return ServingEngine(
            model, params, num_slots=2, max_seq=32, page_size=8,
            num_pages=NUM_RESERVED_PAGES + 5, **kw,
        )

    # fresh single-request reference (ample pool, chunking irrelevant)
    ref = Request(uid=0, prompt=long_prompt.copy(), max_new_tokens=4)
    eng_ref = ServingEngine(model, params, num_slots=1, max_seq=32,
                            page_size=8, prefill_chunk=0)
    eng_ref.submit(ref)
    eng_ref.run_until_done(max_ticks=100)

    reqs = [
        Request(uid=0, prompt=short.copy(), max_new_tokens=10),
        Request(uid=1, prompt=long_prompt.copy(), max_new_tokens=4),
    ]
    eng = tight_engine()
    mid_flight = []
    _drive(eng, reqs, [0, 1],
           probe=lambda e: mid_flight.append(e.stats()["prefill_in_flight"]))
    assert reqs[1].out_tokens == ref.out_tokens
    st = eng.stats()
    assert st["prefill_pauses"] >= 1, st
    assert any(mid_flight), "admission never spanned a tick boundary"

    # the unchunked engine serves the same trace identically (greedy),
    # but must wait for the full grant: the chunked engine admits earlier
    eng_off = tight_engine(prefill_chunk=0)
    reqs_off = [
        Request(uid=0, prompt=short.copy(), max_new_tokens=10),
        Request(uid=1, prompt=long_prompt.copy(), max_new_tokens=4),
    ]
    _drive(eng_off, reqs_off, [0, 1])
    assert [r.out_tokens for r in reqs_off] == [r.out_tokens for r in reqs]
    assert eng.queue_wait_ticks <= eng_off.queue_wait_ticks


def test_preempt_during_prefill_rolls_back_and_retries_bit_identically():
    """A mid-prefill admission is the cheapest preemption victim: when a
    running request needs its pages, the admission is rolled back (pages
    released, request requeued) and retried later — possibly into another
    row — with the stream unchanged."""
    cfg, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "dense", "paged"
    )
    prompts = _prompts(cfg.vocab_size, [8, 28], seed=7)

    def run(prefill_chunk):
        eng = ServingEngine(
            model, params, num_slots=2, max_seq=32, page_size=8,
            num_pages=NUM_RESERVED_PAGES + 5, prefill_chunk=prefill_chunk,
        )
        reqs = [
            Request(uid=0, prompt=prompts[0].copy(), max_new_tokens=20),
            Request(uid=1, prompt=prompts[1].copy(), max_new_tokens=3),
        ]
        slots_seen = []
        _drive(eng, reqs, [0, 1],
               probe=lambda e: slots_seen.append(
                   e._inflight.slot if e._inflight is not None else None))
        return [r.out_tokens for r in reqs], eng, slots_seen

    s_chunk, eng, slots_seen = run(8)
    s_off, _, _ = run(0)
    assert s_chunk == s_off
    st = eng.stats()
    assert st["prefill_aborts"] >= 1, st
    assert st["prefill_pauses"] >= 1, st
    # the long request's prefill was in flight across ticks before the abort
    assert any(s is not None for s in slots_seen)
    # pool hygiene after the rollback dance
    assert eng.pool.num_used == 0 and not eng.tables.pages


def test_resume_pauses_at_chunk_boundary_when_pool_runs_dry():
    """A preempted request's re-prefill routes through the same per-chunk
    claim/pause/rollback machinery as admission: when the pool runs dry
    mid-resume, the resume pauses at a chunk boundary (instead of
    blocking until its full footprint fits) and completes as pages free —
    with its stream bit-identical to an ample-pool run."""
    cfg, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "dense", "paged"
    )
    prompts = _prompts(cfg.vocab_size, [8, 24], seed=11)

    def run(**kw):
        tracer = Tracer()
        eng = ServingEngine(
            model, params, num_slots=2, max_seq=32, page_size=8,
            prefill_chunk=8, tracer=tracer, **kw,
        )
        reqs = [
            Request(uid=0, prompt=prompts[0].copy(), max_new_tokens=20),
            Request(uid=1, prompt=prompts[1].copy(), max_new_tokens=6),
        ]
        _drive(eng, reqs, [0, 0])
        return [r.out_tokens for r in reqs], eng, tracer

    s_ref, _, _ = run()
    s, eng, tracer = run(num_pages=NUM_RESERVED_PAGES + 5)
    assert s == s_ref
    assert eng.preemptions >= 1 and eng.resumes >= 1
    pauses = [e for e in tracer.events("prefill_pause")
              if e.data.get("resume")]
    assert pauses, "no resume ever paused mid-re-prefill"
    # a paused resume keeps partial progress: done > 0 at pause time
    assert any(e.data["done"] > 0 for e in pauses)
    assert eng.pool.num_used == 0 and not eng.tables.pages


def test_chunked_prefill_skips_shared_resident_chunks():
    """With prefix sharing on, chunks fully covered by already-resident
    shared prompt pages never run — the second sharer prefills only its
    divergent tail — and streams match the unshared engine."""
    cfg, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged"
    )
    rng = np.random.default_rng(8)
    prefix = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = [
        np.concatenate([prefix, rng.integers(0, cfg.vocab_size, 4).astype(np.int32)])
        for _ in range(3)
    ]

    def run(share):
        eng = ServingEngine(model, params, num_slots=3, max_seq=32,
                            page_size=8, share_prefix=share)
        reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done(max_ticks=200)
        return [r.out_tokens for r in reqs], eng

    s_plain, _ = run(False)
    s_shared, eng = run(True)
    assert s_shared == s_plain
    st = eng.stats()
    assert st["shared_page_hits"] == 4     # 2 full prefix pages x 2 sharers
    # two later sharers skip their two fully-shared 8-token chunks each
    assert st["prefill_chunks_skipped"] == 4, st
    assert eng.pool.num_used == 0 and not eng._prefix_map


# ---------------------------------------------------------------------------
# memory property: no O(max_prompt) staging tensor in the chunk HLO
# ---------------------------------------------------------------------------
def test_chunk_call_lowering_holds_no_max_prompt_tensor():
    """The one-shot bucketed prefill stages a (1, bucket, ...) slab row
    cache — O(max_prompt).  The chunk call's computation must contain no
    tensor with a prompt-extent axis at all: its inputs are the page pool,
    one chunk of tokens, and a narrow block table."""
    max_seq = 96  # marker value distinct from every smoke model dimension
    cfg, model, params = _model_and_params(
        "codeqwen15_7b", "ssa", "packed", "paged"
    )
    chunk, ps = 8, 8
    cache = model.init_cache(
        1, max_seq, layout="paged",
        num_pages=NUM_RESERVED_PAGES + 4, page_size=ps,
    )
    batch = {
        "tokens": jnp.zeros((1, chunk), jnp.int32),
        "positions": jnp.arange(8, 8 + chunk, dtype=jnp.int32)[None],
    }
    # narrow the block table to the 2 pages the chunk spans (what the
    # engine's bucketed width would pass)
    cache = [
        {k: (v[:, :, :2] if k == "bt" else v) for k, v in d.items()}
        for d in cache
    ]
    f = jax.jit(
        lambda p, b, c, i, s: model.decode_step(
            p, b, c, i, seeds=s, logits_at=jnp.asarray(chunk - 1)
        )
    )
    text = f.lower(
        params, batch, cache,
        jnp.full((1,), 8, jnp.int32), jnp.zeros((1,), jnp.uint32),
    ).as_text()
    markers = (f"x{max_seq}x", f"<{max_seq}x")
    assert not any(m in text for m in markers), (
        "chunked prefill lowering contains a max_seq-extent staging tensor"
    )
    # control: the one-shot bucketed prefill DOES stage O(bucket) rows
    slab_row = model.init_cache(1, max_seq)
    fb = jax.jit(
        lambda p, b, c, s: model.prefill(
            p, b, c, logits_at=jnp.asarray(7), seeds=s
        )
    )
    full_batch = {
        "tokens": jnp.zeros((1, max_seq), jnp.int32),
        "positions": jnp.arange(max_seq, dtype=jnp.int32)[None],
    }
    text_slab = fb.lower(
        params, full_batch, slab_row, jnp.zeros((1,), jnp.uint32)
    ).as_text()
    assert any(m in text_slab for m in markers)


def test_chunk_compile_signatures_stay_bounded():
    """Many distinct prompt lengths compile O(log chunk) partial-chunk
    buckets x O(log pages) table widths, not one signature per length."""
    prompts = _prompts(256, [3, 4, 5, 6, 7, 9, 11, 12, 17, 19, 23, 29],
                       seed=9)
    _, eng = _serve(prompts=prompts, slots=2, max_seq=32, page_size=8,
                    max_new=3)
    assert eng.stats()["chunked_prefills"] == len(prompts)
    # buckets {2,4,8} x widths {1,2,4} at most
    assert len(eng._chunk_signatures) <= 9, sorted(eng._chunk_signatures)
