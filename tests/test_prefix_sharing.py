"""Copy-on-write prefix sharing over the paged KV cache.

The contract under test: with ``share_prefix=True``, requests holding the
same seed and a common prompt prefix map the same *physical* pages in
their block tables (asserted via pool refcounts), emit token streams
bit-identical to the unshared engine, and a page is copied the moment an
owner would write into it (sliding-window wrap) so shared pages stay
pristine.  Correct precisely because RNG contract v2 made draws independent
of which row or page a token lives in: two prefills of the same (seed,
tokens) prefix produce byte-identical cache rows.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import Request, ServingEngine


def _cfg(arch="codeqwen15_7b", storage="packed", layout="paged"):
    cfg = get_smoke_config(arch)
    return dataclasses.replace(
        cfg,
        attention=dataclasses.replace(
            cfg.attention,
            impl="ssa",
            spike_storage=storage,
            cache_layout=layout,
        ),
    )


def _shared_prompts(vocab, n, prefix_len, suffix_len, seed=0):
    """n prompts sharing a `prefix_len`-token system prompt."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, prefix_len).astype(np.int32)
    return [
        np.concatenate(
            [prefix, rng.integers(0, vocab, suffix_len).astype(np.int32)]
        )
        for _ in range(n)
    ]


def _serve(cfg, prompts, *, share, slots=3, max_seq=32, max_new=5,
           page_size=8, seeds=None, **kw):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(
        model, params, num_slots=slots, max_seq=max_seq,
        page_size=page_size, share_prefix=share, **kw,
    )
    reqs = [
        Request(uid=i, prompt=p, max_new_tokens=max_new,
                seed=None if seeds is None else seeds[i])
        for i, p in enumerate(prompts)
    ]
    for r in reqs:
        eng.submit(r)
    # step manually so mid-run pool state can be asserted
    mid = None
    ticks = 0
    while eng.has_pending_work:
        eng.step()
        ticks += 1
        if mid is None and len(eng.active) >= min(slots, len(prompts)):
            mid = {
                "shared_pages": eng.pool.num_shared,
                "tables": {
                    s: list(eng.tables.pages.get(s, []))
                    for s in eng.active
                },
            }
        assert ticks < 300, "engine failed to drain"
    return [r.out_tokens for r in reqs], eng, mid


def test_share_prefix_requires_paged_layout():
    cfg = _cfg(layout="slab")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="share_prefix"):
        ServingEngine(model, params, num_slots=1, max_seq=32,
                      share_prefix=True)


@pytest.mark.parametrize("storage", ["packed", "dense"])
def test_shared_prefix_maps_same_physical_pages_bit_identically(storage):
    """Acceptance check: three requests with a 16-token shared system
    prompt physically share its two pages (refcounts > 1, block tables
    alias) and stream exactly what the unshared engine streams."""
    cfg = _cfg(storage=storage)
    prompts = _shared_prompts(cfg.vocab_size, 3, prefix_len=16, suffix_len=4)
    s_plain, e_plain, _ = _serve(cfg, prompts, share=False)
    s_shared, e_shared, mid = _serve(cfg, prompts, share=True)
    assert s_shared == s_plain
    st = e_shared.stats()
    # 2 full prefix pages x 2 later arrivals claimed from the map
    assert st["shared_page_hits"] == 4
    assert mid is not None and mid["shared_pages"] >= 1
    # the block tables of concurrently-active sharers alias the same ids
    tables = list(mid["tables"].values())
    assert len(tables) >= 2
    first_two = {tuple(t[:2]) for t in tables}
    assert len(first_two) == 1, first_two
    # fewer physical pages at peak than the unshared run
    assert st["peak_pages_used"] < e_plain.stats()["peak_pages_used"]
    # pool hygiene: everything drains, registrations retire with the pages
    assert e_shared.pool.num_used == 0
    assert not e_shared._prefix_map and not e_shared._page_key


def test_sharing_requires_matching_seed():
    """Pages are keyed by (seed, token prefix): same prompt prefix under
    different request seeds samples different prefill spikes, so it must
    NOT share."""
    cfg = _cfg()
    prompts = _shared_prompts(cfg.vocab_size, 2, prefix_len=16, suffix_len=3)
    _, eng, _ = _serve(cfg, prompts, share=True, seeds=[111, 222])
    assert eng.stats()["shared_page_hits"] == 0
    # equal seeds restore sharing
    _, eng2, _ = _serve(cfg, prompts, share=True, seeds=[111, 111])
    assert eng2.stats()["shared_page_hits"] == 2


def test_window_wrap_copies_shared_page_and_stays_bit_identical():
    """gemma2's sliding-window layers wrap their rolling write offset back
    into the shared prompt-prefix page once pos >= window: the engine must
    copy-on-write (divergence) and keep streams identical to the unshared
    engine."""
    cfg = _cfg("gemma2_9b")
    prompts = _shared_prompts(cfg.vocab_size, 2, prefix_len=8, suffix_len=3,
                              seed=4)
    # window=16 in the smoke config, held in a ring of 24 rows (the window
    # plus one 8-row page): 11-token prompts + 16 generated tokens cross
    # it, wrapping writes into page 0 (the shared one)
    s_plain, _, _ = _serve(cfg, prompts, share=False, slots=2, max_new=16)
    s_shared, eng, _ = _serve(cfg, prompts, share=True, slots=2, max_new=16)
    assert s_shared == s_plain
    st = eng.stats()
    assert st["shared_page_hits"] >= 1
    assert st["cow_copies"] >= 1
    assert eng.pool.num_used == 0 and not eng._prefix_map


def test_sharing_survives_preemption_and_resume():
    """Under page pressure a sharer can be preempted; its resume re-claims
    the still-resident prefix pages and replays — streams unchanged vs the
    unshared tight engine (greedy)."""
    from repro.attention import NUM_RESERVED_PAGES

    cfg = _cfg()
    prompts = _shared_prompts(cfg.vocab_size, 3, prefix_len=8, suffix_len=3,
                              seed=7)
    kw = dict(slots=3, max_new=12,
              num_pages=NUM_RESERVED_PAGES + 6)
    s_plain, e_plain, _ = _serve(cfg, prompts, share=False, **kw)
    s_shared, eng, _ = _serve(cfg, prompts, share=True, **kw)
    assert eng.stats()["shared_page_hits"] >= 2
    assert s_shared == s_plain
    assert eng.pool.num_used == 0 and not eng._prefix_map


def test_stats_surface_sharing_counters():
    cfg = _cfg()
    prompts = _shared_prompts(cfg.vocab_size, 2, prefix_len=8, suffix_len=2)
    _, eng, _ = _serve(cfg, prompts, share=True)
    st = eng.stats()
    for key in ("share_prefix", "shared_pages_now", "shared_page_hits",
                "cow_copies", "peak_pages_used", "migrations"):
        assert key in st, key
    assert st["share_prefix"] is True
