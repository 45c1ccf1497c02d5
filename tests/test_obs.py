"""Observability subsystem: tracer ring/sinks, metrics primitives, Perfetto
export, engine stats schema + monotonicity, and the two load-bearing
guarantees — tracing changes no token, and the event stream of a pinned
scheduler scenario is itself a golden fixture.

The golden event fixture (``tests/golden/events-*.json``) pins the
*scheduler's observable behaviour* — admits, preemptions, migrations,
resumes, shared-prefix hits, page grants/releases — the same way the token
goldens pin numerics.  Regenerate with ``--regen-golden`` only for an
intentional scheduler change (and say so in the commit).
"""
import json

import jax
import numpy as np
import pytest

from repro.attention import NUM_RESERVED_PAGES
from repro.configs import get_smoke_config, with_overrides
from repro.models import build_model
from repro.obs import (
    EVENT_KINDS,
    Counter,
    Event,
    Gauge,
    Histogram,
    InMemorySink,
    JSONLSink,
    MetricsRegistry,
    Tracer,
    export_perfetto,
    to_chrome_trace,
)
from repro.serving import Request, ServingEngine


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------
def test_tracer_emit_ring_and_drop_accounting():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.emit("decode_tick", tick=i)
    assert tr.events_emitted == 10
    assert tr.events_dropped == 6
    assert [e.tick for e in tr.events()] == [6, 7, 8, 9]
    assert [e.tick for e in tr.tail(2)] == [8, 9]


def test_tracer_rejects_unknown_kind():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.emit("not_a_kind", tick=0)
    assert "decode_tick" in EVENT_KINDS and "phase" in EVENT_KINDS


def test_tracer_kind_filter_and_signatures_exclude_phases():
    tr = Tracer()
    tr.emit("submit", tick=0, uid=1, prompt_len=3)
    tr.emit("phase", tick=0, phase="schedule", dur_s=0.01)
    tr.emit("finish", tick=5, uid=1, row=0, reason="eos")
    assert [e.kind for e in tr.events("phase")] == ["phase"]
    sigs = tr.signatures()
    assert [s[0] for s in sigs] == ["submit", "finish"]
    all_sigs = tr.signatures(include_phases=True)
    assert [s[0] for s in all_sigs] == ["submit", "phase", "finish"]


def test_event_signature_excludes_timing_keys():
    ev = Event(kind="phase", tick=3, wall=123.456,
               data={"phase": "sample", "dur_s": 0.5, "wall_s": 99.0})
    sig = ev.signature()
    assert sig[0] == "phase" and sig[1] == 3
    assert "dur_s" not in sig[-1] and "wall_s" not in sig[-1]
    assert sig[-1]["phase"] == "sample"
    # and the wall clock itself never appears in a signature
    assert 123.456 not in sig


def test_sinks_receive_events_and_jsonl_roundtrips(tmp_path):
    mem = InMemorySink()
    path = tmp_path / "events.jsonl"
    tr = Tracer(sinks=(mem, JSONLSink(str(path))))
    tr.emit("submit", tick=0, uid=7, prompt_len=4)
    tr.emit("finish", tick=9, uid=7, row=1, reason="eos")
    tr.close()
    assert [e.kind for e in mem.events] == ["submit", "finish"]
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [d["kind"] for d in lines] == ["submit", "finish"]
    assert lines[0]["uid"] == 7 and lines[0]["data"]["prompt_len"] == 4


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------
def test_counter_and_gauge_semantics():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(3)
    g.set(1)
    assert g.value == 1 and g.max == 3


def test_histogram_percentiles_and_determinism():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.min == 1.0 and h.max == 100.0
    # nearest-rank: rank(q) = round(q/100 * (n-1)) over the sorted samples
    assert h.percentile(50) == 51.0
    assert h.percentile(95) == 95.0
    assert h.percentile(0) == 1.0 and h.percentile(100) == 100.0
    s = h.summary()
    assert s["count"] == 100 and s["mean"] == pytest.approx(50.5)
    # identical observation sequences -> identical summaries (no RNG)
    h2 = Histogram()
    for v in range(1, 101):
        h2.observe(float(v))
    assert h.summary() == h2.summary()


def test_histogram_decimation_is_bounded_and_keeps_extremes():
    h = Histogram(max_samples=64)
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000
    assert len(h._samples) <= 64
    # exact extremes survive via the streaming min/max
    assert h.min == 0.0 and h.max == 9999.0
    # percentiles stay sane estimates under decimation
    assert 3000 <= h.percentile(50) <= 7000


def test_registry_snapshot_schema():
    m = MetricsRegistry()
    m.inc("ticks", 3)
    m.gauge("occupancy").set(0.5)
    m.observe("ttft_ticks", 2.0)
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["ticks"] == 3
    assert snap["gauges"]["occupancy"] == {"value": 0.5, "max": 0.5}
    assert snap["histograms"]["ttft_ticks"]["count"] == 1
    # snapshot is frozen: mutating the registry afterwards must not alter it
    m.inc("ticks")
    assert snap["counters"]["ticks"] == 3


# ---------------------------------------------------------------------------
# perfetto export
# ---------------------------------------------------------------------------
def _demo_events():
    tr = Tracer()
    tr.emit("submit", tick=0, uid=0, prompt_len=4, queued=1)
    tr.emit("admit", tick=0, uid=0, row=0, prompt_len=4, wait_ticks=0)
    tr.emit("phase", tick=0, phase="schedule", dur_s=0.002)
    tr.emit("decode_tick", tick=0, active=1, rows=[[0, 0]], pages_used=2)
    tr.emit("phase", tick=0, phase="dispatch", dur_s=0.01)
    tr.emit("preempt", tick=1, uid=0, row=0, tokens=5)
    tr.emit("resume", tick=2, uid=0, row=1, tokens=5)
    tr.emit("finish", tick=3, uid=0, row=1, tokens=8, reason="eos")
    return tr.events()


def test_chrome_trace_structure_and_span_balance(tmp_path):
    doc = to_chrome_trace(_demo_events())
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert all(e["ph"] in {"X", "i", "M", "C"} for e in evs)
    # every X slice carries non-negative ts and dur
    for e in evs:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    # the request lifeline covers queued -> running -> preempted -> running
    names = [e["name"] for e in evs if e["ph"] == "X" and e["pid"] == 2]
    assert names.count("running") == 2
    assert "queued" in names and "preempted" in names
    # export writes loadable JSON
    out = tmp_path / "trace.json"
    export_perfetto(_demo_events(), str(out))
    loaded = json.loads(out.read_text())
    assert loaded["traceEvents"]


def test_chrome_trace_counter_tracks_from_decode_ticks():
    doc = to_chrome_trace(_demo_events())
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert any(e["args"].get("active") == 1 for e in counters)
    assert any(e["args"].get("pages_used") == 2 for e in counters)


def test_chrome_trace_replica_tagged_events_get_own_process():
    tr = Tracer()
    for i in (0, 1):
        tr.emit("phase", tick=0, phase="dispatch", dur_s=0.01, replica=i)
        tr.emit("decode_tick", tick=0, active=1, pages_used=3 + i,
                replica=i)
    doc = to_chrome_trace(tr.events())
    evs = doc["traceEvents"]
    # one process per replica, named after it
    names = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names[100] == "replica 0" and names[101] == "replica 1"
    # each replica's phase slices and counters land in its own process
    for i in (0, 1):
        assert any(e["ph"] == "X" and e["pid"] == 100 + i
                   and e["name"] == "dispatch" for e in evs)
        assert any(e["ph"] == "C" and e["pid"] == 100 + i
                   and e["args"].get("pages_used") == 3 + i for e in evs)
    # untagged traces never allocate replica processes
    plain = to_chrome_trace(_demo_events())
    assert all(e["pid"] < 100 for e in plain["traceEvents"])


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------
def _paged_cfg():
    return with_overrides(
        get_smoke_config("codeqwen15_7b"),
        attention__impl="ssa",
        attention__spike_storage="packed",
        attention__cache_layout="paged",
    )


def _drive(eng, reqs, arrivals, max_ticks=300):
    done, tick, i = [], 0, 0
    while i < len(reqs) or eng.has_pending_work:
        while i < len(reqs) and arrivals[i] <= tick:
            eng.submit(reqs[i])
            i += 1
        done.extend(eng.step())
        tick += 1
        assert tick < max_ticks
    return done


def _burst(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                int(rng.integers(3, 10))).astype(np.int32),
            max_new_tokens=int(rng.integers(3, 7)),
        )
        for i in range(n)
    ]


@pytest.mark.parametrize("sync_device", [False, True])
def test_tracing_preserves_token_streams(sync_device):
    """The zero-interference guarantee: a traced engine (even with
    per-phase device sync) samples exactly the tokens an untraced one
    does."""
    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    streams = {}
    for name, tracer in (
        ("plain", None), ("traced", Tracer(sync_device=sync_device))
    ):
        eng = ServingEngine(
            model, params, num_slots=2, max_seq=32,
            page_size=8, num_pages=NUM_RESERVED_PAGES + 8, tracer=tracer,
        )
        reqs = _burst(cfg)
        _drive(eng, reqs, arrivals=[0, 0, 1, 2])
        streams[name] = [list(r.out_tokens) for r in reqs]
    assert streams["plain"] == streams["traced"]


def test_stats_schema_and_monotone_ticks():
    cfg = get_smoke_config("codeqwen15_7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=2, max_seq=32)
    base_keys = {
        "layout", "ticks", "active", "queued", "queue_wait_ticks",
        "kv_cache_nbytes", "occupancy", "requests_submitted",
        "requests_finished", "tokens_sampled", "compile_events",
    }
    s0 = eng.stats()
    assert set(s0) == base_keys
    reqs = _burst(cfg, n=2)
    _drive(eng, reqs, arrivals=[0, 0])
    s1 = eng.stats()
    assert set(s1) == set(s0)
    assert s1["ticks"] > s0["ticks"]
    assert s1["requests_finished"] == 2
    assert s1["tokens_sampled"] == sum(len(r.out_tokens) for r in reqs)
    assert s1["compile_events"] >= 1


def test_stats_schema_gates_cache_keys_on_prefix_cache():
    """The six cache_* stats keys appear iff the persistent prefix cache
    is enabled — the paged key set is otherwise byte-identical, so
    existing schema consumers never see them."""
    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    kw = dict(num_slots=2, max_seq=32, page_size=8,
              num_pages=NUM_RESERVED_PAGES + 8, share_prefix=True)
    plain = ServingEngine(model, params, **kw)
    cached = ServingEngine(model, params, prefix_cache_pages=4, **kw)
    cache_keys = {
        "prefix_cache_pages", "cached_pages_now", "cache_inserts",
        "cache_hits", "cache_misses", "cache_evictions",
    }
    assert set(cached.stats()) == set(plain.stats()) | cache_keys
    assert not cache_keys & set(plain.stats())


def test_snapshot_bundles_stats_metrics_and_trace():
    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(
        model, params, num_slots=2, max_seq=32, page_size=8,
        num_pages=NUM_RESERVED_PAGES + 8, tracer=Tracer(),
    )
    _drive(eng, _burst(cfg, n=3), arrivals=[0, 1, 1])
    snap = eng.snapshot()
    assert set(snap) == {"stats", "metrics", "trace"}
    assert snap["stats"]["requests_finished"] == 3
    hists = snap["metrics"]["histograms"]
    assert hists["ttft_ticks"]["count"] == 3
    assert hists["intertoken_ticks"]["count"] >= 1
    for ph in ("schedule", "host_stage", "dispatch", "sample"):
        assert hists[f"phase_{ph}_s"]["count"] >= 1
    assert snap["trace"]["events_dropped"] == 0
    # untraced engines omit the trace section and skip phase timings
    eng2 = ServingEngine(model, params, num_slots=1, max_seq=32, page_size=8,
                         num_pages=NUM_RESERVED_PAGES + 8)
    snap2 = eng2.snapshot()
    assert set(snap2) == {"stats", "metrics"}


def test_legacy_counter_properties_mirror_registry():
    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=1, max_seq=32, page_size=8,
                        num_pages=NUM_RESERVED_PAGES + 8)
    _drive(eng, _burst(cfg, n=2, seed=3), arrivals=[0, 0])
    assert eng.steps_run == eng.metrics.counter("ticks").value > 0
    assert eng.preemptions == eng.metrics.counter("preemptions").value
    assert (eng.max_concurrency_seen
            == eng.metrics.gauge("concurrency").max == 1)
    with pytest.raises(AttributeError):
        eng.steps_run = 5  # read-only: the registry is the source of truth


# ---------------------------------------------------------------------------
# profiler spans and program names (what a device trace reads)
# ---------------------------------------------------------------------------
def _profiled_host_events(tmp_path, tracer):
    """Serve two chunked admissions and their decode ticks under
    ``jax.profiler.trace``; returns every host event as
    ``(name, start_ns, end_ns, line)``."""
    from jax.profiler import ProfileData

    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(
        model, params, num_slots=2, max_seq=32, page_size=8,
        num_pages=NUM_RESERVED_PAGES + 8, tracer=tracer,
    )
    rng = np.random.default_rng(5)

    def burst(uid0):
        # 12-token prompts: two chunks of one 8-row page each admission
        return [Request(uid=uid0 + i, max_new_tokens=3,
                        prompt=rng.integers(0, cfg.vocab_size, 12)
                        .astype(np.int32)) for i in range(2)]

    _drive(eng, burst(0), arrivals=[0, 0])      # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _drive(eng, burst(2), arrivals=[0, 0])
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                            (plane.name, i)) for e in line.events]
    return events


def _within(events, inner, outers):
    """(how many ``inner`` spans lie inside an ``outers`` span on the same
    thread, how many ``inner`` spans there are)."""
    ins = [e for e in events if e[0] == inner]
    outs = [e for e in events if e[0] in outers]
    n = sum(any(o[3] == line and o[1] <= a and b <= o[2] for o in outs)
            for _, a, b, line in ins)
    return n, len(ins)


def test_tick_phases_are_profiler_spans(tmp_path):
    """A traced engine labels each tick phase on the profiler's clock, with
    every program launch innermost in its dispatch span.  Two admissions
    of two chunks each, then two decode ticks."""
    events = _profiled_host_events(tmp_path, Tracer())
    names = {e[0] for e in events}
    assert {"repro/schedule", "repro/admit", "repro/host_stage",
            "repro/dispatch", "repro/sample", "repro/fetch_tokens"} <= names
    assert _within(events, "repro/decode_dispatch", {"repro/dispatch"}) == (2, 2)
    assert _within(events, "repro/prefill_chunk_dispatch",
                   {"repro/admit"}) == (4, 4)
    assert _within(events, "repro/admit", {"repro/schedule"}) == (2, 2)
    # a tick's tokens are fetched in sample, an admission's first token
    # inside admit
    assert _within(events, "repro/fetch_tokens", {"repro/sample"}) == (2, 4)
    assert _within(events, "repro/fetch_tokens",
                   {"repro/sample", "repro/admit"}) == (4, 4)


def test_untraced_engine_opens_no_profiler_span(tmp_path):
    """With no tracer the tick opens no ``repro/`` span (and the trace
    still records the engine's named programs being launched)."""
    events = _profiled_host_events(tmp_path, None)
    names = {e[0] for e in events}
    assert not [n for n in names if n.startswith("repro/")]
    assert "PjitFunction(serve_decode_seeded)" in names


def test_engine_programs_lower_to_named_modules():
    """Decode and chunk programs are named after their jit keys; the
    draft's are named apart from the target's."""
    from repro.serving import DraftConfig

    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, num_slots=2, max_seq=32, page_size=8,
                        num_pages=NUM_RESERVED_PAGES + 8)
    seen = {}
    for attr in ("_decode", "_chunk"):
        def spy(*args, _fn=getattr(eng, attr), _attr=attr):
            seen.setdefault(_attr, (_fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)))
            return _fn(*args)
        setattr(eng, attr, spy)
    _drive(eng, _burst(cfg, n=1), arrivals=[0])
    for attr, module in (("_decode", "jit_serve_decode_seeded"),
                         ("_chunk", "jit_serve_chunk")):
        fn, args = seen[attr]
        assert f"module @{module} " in fn.lower(*args).as_text()
    spec = ServingEngine(model, params, num_slots=2, max_seq=32, page_size=8,
                         num_pages=NUM_RESERVED_PAGES + 8,
                         draft=DraftConfig(k=2))
    assert spec._decode.__name__ == "serve_decode_seeded"
    assert spec._draft_decode.__name__ == "serve_draft_decode_seeded"
    assert spec._draft_chunk.__name__ == "serve_draft_chunk"


# ---------------------------------------------------------------------------
# golden event stream: pinned preempt/migrate/resume/share scenario
# ---------------------------------------------------------------------------
def test_golden_event_stream_paged_scheduler(golden):
    """Three sharers of one 16-token system prompt through a 6-usable-page
    pool: admits, shared-prefix hits, preemption under growth pressure,
    migration + replay on resume — the full lifecycle vocabulary — must
    reproduce the committed event sequence exactly."""
    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    system = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    reqs = [
        Request(uid=uid, prompt=system.copy(), max_new_tokens=14)
        for uid in range(3)
    ]
    tracer = Tracer()
    eng = ServingEngine(
        model, params, num_slots=3, max_seq=32, page_size=8,
        num_pages=NUM_RESERVED_PAGES + 6, share_prefix=True,
        prefill_chunk=8, tracer=tracer,
    )
    _drive(eng, reqs, arrivals=[0, 0, 2])
    kinds = {sig[0] for sig in tracer.signatures()}
    # the scenario must actually exercise the interesting lifecycle arcs
    assert {"admit", "shared_prefix_hit", "preempt", "migrate", "resume",
            "replay", "page_grant", "page_share", "page_release",
            "finish"} <= kinds
    golden.check(
        "events-codeqwen-ssa-packed-paged-shared",
        {
            "scenario": {
                "arch": "codeqwen15_7b", "impl": "ssa", "storage": "packed",
                "slots": 3, "max_seq": 32, "page_size": 8,
                "usable_pages": 6, "prefill_chunk": 8,
                "share_prefix": True, "arrivals": [0, 0, 2],
                "prompt": "16-token shared system prompt, rng seed 3",
            },
            "signatures": tracer.signatures(),
            "streams": {str(r.uid): list(map(int, r.out_tokens))
                        for r in reqs},
        },
    )


def test_golden_event_stream_prefix_cache_lifecycle(golden):
    """The persistent-cache event vocabulary, pinned end to end: a sharer
    drains (release → cache_insert parks its pages), a second sharer
    arrives after the drain (cache_hit revives them), then a non-sharing
    request's footprint forces pressure reclamation (cache_evict) instead
    of a preemption or pause."""
    cfg = _paged_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    system = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    stranger = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    reqs = [
        Request(uid=0, prompt=np.concatenate(
            [system, np.array([5, 6, 7], np.int32)]), max_new_tokens=4),
        Request(uid=1, prompt=np.concatenate(
            [system, np.array([2, 9], np.int32)]), max_new_tokens=4),
        Request(uid=2, prompt=stranger, max_new_tokens=8, seed=99),
    ]
    tracer = Tracer()
    eng = ServingEngine(
        model, params, num_slots=2, max_seq=32, page_size=8,
        num_pages=NUM_RESERVED_PAGES + 5, share_prefix=True,
        prefix_cache_pages=4, prefill_chunk=8, tracer=tracer,
    )
    _drive(eng, reqs, arrivals=[0, 15, 30])
    sigs = tracer.signatures()
    kinds = [sig[0] for sig in sigs]
    assert {"cache_insert", "cache_hit", "cache_evict"} <= set(kinds)
    # lifecycle order: a release parks pages before the first revival,
    # which precedes the pressure eviction
    assert (kinds.index("page_release") < kinds.index("cache_insert")
            < kinds.index("cache_hit") < kinds.index("cache_evict"))
    st = eng.stats()
    assert st["preemptions"] == 0 and st["prefill_pauses"] == 0
    golden.check(
        "events-codeqwen-ssa-packed-paged-prefix-cache",
        {
            "scenario": {
                "arch": "codeqwen15_7b", "impl": "ssa", "storage": "packed",
                "slots": 2, "max_seq": 32, "page_size": 8,
                "usable_pages": 5, "prefill_chunk": 8,
                "share_prefix": True, "prefix_cache_pages": 4,
                "arrivals": [0, 15, 30],
                "prompt": "16-token shared system prompt, rng seed 3; "
                          "uid 2 is a 24-token non-sharing stranger",
            },
            "signatures": sigs,
            "streams": {str(r.uid): list(map(int, r.out_tokens))
                        for r in reqs},
        },
    )
