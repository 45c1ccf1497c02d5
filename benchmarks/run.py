"""Benchmark harness: one function per paper table + kernel microbench +
roofline summary.  Prints ``name,us_per_call,derived`` CSV lines.

``--compare-storage`` runs the dense-vs-packed spike-storage comparison
(modeled KV decode traffic + measured cache bytes and decode latency on a
smoke SSA model) — the in-simulator reproduction of the paper's
memory-access-reduction claim.

``--compare-backends`` times one decode step per attention backend
(ssa-xla / ssa-fused / ssa-fused-packed) on the smoke config, pairs it with
the modeled bytes-moved for the backend's KV dataflow, and appends a JSON
record to ``benchmarks/perf_trajectory.jsonl`` so the per-PR perf history
accumulates.

``--compare-paging`` serves one synthetic bursty trace through a slab
engine and through a paged engine holding the *same pool bytes* but more
decode rows, and writes kv bytes allocated / achieved concurrency /
tokens-per-sec / preemption counters to ``benchmarks/BENCH_paging.json``.

``--compare-sharing`` serves a bursty multi-tenant trace (Zipf-skewed
tenant popularity, drain-separated arrival waves) through unshared,
CoW-shared, and persistently-cached paged engines holding the same tight
pool, and writes prefill-dispatch counts, cache hit/eviction counters and
the cached-vs-shared dispatch reduction to
``benchmarks/BENCH_sharing.json``.

``--compare-prefill`` serves an over-long prompt through a paged engine
with one-shot (slab-staged) vs chunked (direct-to-page) prefill and writes
peak prefill staging bytes + admission latency to
``benchmarks/BENCH_prefill.json``.

``--compare-spec`` serves one pinned greedy workload through a paged
engine plain and with self-speculative decode (reduced-time-step SSA
draft, exact position-keyed verification) and writes target dispatches
per committed token / acceptance statistics / stream identity to
``benchmarks/BENCH_spec.json``.

``--compare-sharded`` serves one pinned bursty workload through an
unsharded engine, 2- and 4-way tensor-parallel engines (head-sharded KV
over a device mesh), and 1x2 / 2x2 replica x shard configurations
holding the same total usable pool pages, asserts every configuration's
greedy streams are bit-identical, and writes achieved concurrency /
queue-wait / per-replica dispatch counts to
``benchmarks/BENCH_sharded.json`` (needs >= 4 devices; force them on CPU
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

``--trace-out PATH.json`` (any serving compare mode) attaches a
:class:`repro.obs.Tracer` to every engine and exports one Perfetto /
Chrome-trace JSON per engine (``PATH.<bench>_<engine>.json`` — load at
``ui.perfetto.dev``).  Every compare mode appends its summary record to
``benchmarks/perf_trajectory.jsonl``; ``benchmarks/regression_gate.py``
re-runs the deterministic compares and diffs them against the committed
``benchmarks/BENCH_baseline.json``."""
from __future__ import annotations

import argparse
import json
import os
import time

# set by main(--trace-out); compare modes export one Perfetto file per
# engine run under this stem when set
_TRACE_OUT: str | None = None


def _bench(fn, iters=10, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6  # us


def _append_trajectory(rec: dict) -> None:
    """Append one summary record to the per-PR perf history."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "perf_trajectory.jsonl"
    )
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _make_tracer(always: bool = False):
    """A Tracer when --trace-out is set (or the caller needs event counts
    regardless — the regression gate diffs the paged engines' event
    totals); None otherwise."""
    if _TRACE_OUT is None and not always:
        return None
    from repro.obs import Tracer

    return Tracer()


def _event_totals(tracer) -> dict:
    """Deterministic event-kind counts of a traced run (``phase`` events
    are timing-only and excluded)."""
    totals: dict[str, int] = {}
    for ev in tracer.events():
        if ev.kind != "phase":
            totals[ev.kind] = totals.get(ev.kind, 0) + 1
    return dict(sorted(totals.items()))


def _export_trace(tracer, label: str) -> None:
    """Write one engine run's Perfetto JSON next to the --trace-out stem."""
    if tracer is None or _TRACE_OUT is None:
        return
    from repro.obs import export_perfetto

    base, ext = os.path.splitext(_TRACE_OUT)
    path = f"{base}.{label}{ext or '.json'}"
    export_perfetto(tracer.events(), path)
    print(f"trace/{label},0,events={tracer.events_emitted};path={path}")


def bench_table2_energy():
    from .energy_model import table2

    r = table2()
    us = _bench(lambda: table2(), iters=50)
    ours, paper = r["ours"], r["paper"]
    for k in ("ANN", "Spikformer", "SSA"):
        print(
            f"table2_energy/{k},{us:.1f},proc_uJ={ours[k]['processing_uJ']:.2f}"
            f";paper={paper[k]['processing_uJ']:.2f}"
            f";mem_uJ={ours[k]['memory_uJ']:.2f};paper_mem={paper[k]['memory_uJ']:.2f}"
        )
    print(
        f"table2_ratios,{us:.1f},proc_ann_over_ssa={r['ratios']['processing_ann_over_ssa']:.2f}"
        f";paper=6.32;mem_spk_over_ssa={r['ratios']['memory_spk_over_ssa']:.2f};paper=1.95"
    )


def bench_table3_latency():
    from .table3_latency import table3

    r = table3()
    f = r["fpga_model"]
    print(
        f"table3_fpga_model,{f['latency_ms'] * 1e3:.2f},"
        f"cycles={f['cycles']};paper_ms={f['paper_latency_ms']};rel_err={f['rel_error']:.3f}"
    )
    j = r["jax_cpu_reference"]
    print(
        f"table3_jax_cpu_ssa,{j['latency_ms'] * 1e3:.1f},"
        f"paper_ssa_cpu_ms={j['paper_ssa_cpu_ms']}"
    )


def bench_ssa_kernel():
    import jax
    import jax.numpy as jnp

    from repro.kernels.ssa_attention.ops import ssa_attention
    from repro.kernels.ssa_attention.ref import ssa_reference

    key = jax.random.PRNGKey(0)
    b, n, d = 8, 256, 64
    q = (jax.random.uniform(key, (b, n, d)) < 0.5).astype(jnp.float32)
    seed = jnp.uint32(1)
    fused = jax.jit(lambda q: ssa_attention(q, q, q, seed, True, None, 128, 128, True))
    ref = jax.jit(lambda q: ssa_reference(q, q, q, seed, causal=True))
    fused(q).block_until_ready()
    ref(q).block_until_ready()
    us_f = _bench(lambda: fused(q).block_until_ready(), iters=5)
    us_r = _bench(lambda: ref(q).block_until_ready(), iters=5)
    print(f"ssa_kernel_interpret,{us_f:.0f},B{b}xN{n}xD{d};interpret_mode=True")
    print(f"ssa_reference_jnp,{us_r:.0f},B{b}xN{n}xD{d};oracle")


def bench_table1_accuracy():
    """Compressed Table-I check; the full 300-step sweep lives in
    examples/train_spiking_vit.py (recorded in EXPERIMENTS.md: ANN 0.833,
    SSA best 0.807)."""
    from .table1_accuracy import train_vit

    steps = 150
    ann = train_vit("ann", 1, steps=steps)
    ssa = train_vit("ssa", 4, steps=steps)
    print(
        f"table1_smoke_ann,{ann['train_s'] * 1e6:.0f},acc={ann['accuracy']:.3f};steps={steps}"
    )
    print(
        f"table1_smoke_ssa_T4,{ssa['train_s'] * 1e6:.0f},acc={ssa['accuracy']:.3f}"
        f";gap={ann['accuracy'] - ssa['accuracy']:.3f};steps={steps}"
        f";full_sweep=examples/train_spiking_vit.py"
    )


def bench_roofline_summary():
    from .roofline import load_records, summarize

    n_ok = n_skip = 0
    worst = None
    for rec in load_records():
        if rec.get("status") == "skip":
            n_skip += 1
            continue
        s = summarize(rec)
        if s:
            n_ok += 1
            # decode cells are inherently memory-bound at ~0 fraction
            # (one token's flops vs a full cache read) — report the worst
            # compute-carrying cell instead
            if s["kind"] == "decode":
                continue
            if worst is None or s["roofline_fraction"] < worst["roofline_fraction"]:
                worst = s
    if worst:
        print(
            f"roofline_cells,{0:.0f},ok={n_ok};skipped={n_skip};"
            f"worst={worst['arch']}/{worst['shape']}"
            f";frac={worst['roofline_fraction']:.3f};dominant={worst['dominant']}"
        )
    else:
        print("roofline_cells,0,none_found=run `python -m repro.launch.dryrun --all`")


def bench_storage_compare():
    """Dense vs packed spike storage: modeled decode traffic + measured
    cache footprint and decode-step latency (smoke SSA model, CPU)."""
    import jax
    import jax.numpy as jnp

    from .energy_model import storage_comparison

    # ---- modeled bytes moved per decode step (per layer/sequence) --------
    rows = storage_comparison(n_ctx=4096, n_kv_heads=8, t=4)
    for d_k, r in rows.items():
        print(
            f"kv_storage_model/dk{d_k},0,"
            f"dense_MB={r['dense']['bytes_moved'] / 2**20:.2f}"
            f";packed_MB={r['packed']['bytes_moved'] / 2**20:.3f}"
            f";moved_ratio={r['moved_ratio']:.1f}"
            f";resident_ratio={r['resident_ratio']:.1f}"
        )
    ok = all(r["moved_ratio"] >= 8.0 for d_k, r in rows.items() if d_k >= 64)
    print(f"kv_storage_model/claim,0,ge8x_for_dk_ge_64={ok}")

    # ---- measured: smoke SSA engine caches + one fused decode step -------
    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model

    cfg = with_overrides(get_smoke_config("codeqwen15_7b"), attention__impl="ssa")
    variants = {
        "dense": build_model(cfg),
        "packed": build_model(with_overrides(cfg, attention__spike_storage="packed")),
    }
    params = variants["dense"].init(jax.random.PRNGKey(0))
    stats = {}
    for name, model in variants.items():
        cache = model.init_cache(4, 64)
        nbytes = sum(int(l.nbytes) for l in jax.tree.leaves(cache))
        batch = {
            "tokens": jnp.zeros((4, 1), jnp.int32),
            "positions": jnp.full((4, 1), 8, jnp.int32),
        }
        idx = jnp.full((4,), 8, jnp.int32)
        step = jax.jit(lambda p, b, c, i, m=model: m.decode_step(p, b, c, i))
        step(params, batch, cache, idx)[0].block_until_ready()
        us = _bench(
            lambda: step(params, batch, cache, idx)[0].block_until_ready(),
            iters=5,
        )
        stats[name] = (nbytes, us)
        print(f"kv_storage_measured/{name},{us:.0f},cache_bytes={nbytes}")
    ratio = stats["dense"][0] / stats["packed"][0]
    print(f"kv_storage_measured/ratio,0,cache_bytes_dense_over_packed={ratio:.2f}")


def bench_backend_compare(record_path: str | None = None):
    """Decode-step time + modeled bytes-moved per attention backend.

    Off-TPU the fused backends run the Pallas kernels in interpret mode, so
    their *latency* here is a correctness probe, not a perf number (the CSV
    marks it); bytes-moved comes from the traffic model and describes the
    fused-kernel dataflow each backend realises, and each backend also
    carries its family's modeled per-block processing energy
    (``energy_model.ATTENTION_ENERGY_BY_IMPL`` — the Table-II methodology
    applied to the addition-only sdsa / qksum families too).  One JSON
    record per backend is appended to ``benchmarks/perf_trajectory.jsonl``,
    plus one record for the spiking-ViT event-stream serving workload
    (prefill-only classification through the paged engine).
    """
    import jax
    import jax.numpy as jnp

    from repro.attention import default_interpret
    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model

    from .energy_model import (
        ATTENTION_ENERGY_BY_IMPL,
        Workload,
        kv_decode_traffic,
    )

    base = with_overrides(get_smoke_config("codeqwen15_7b"), attention__impl="ssa")
    variants = {
        "ssa-xla": with_overrides(base, attention__backend="xla"),
        "ssa-fused": with_overrides(base, attention__backend="fused"),
        "ssa-fused-packed": with_overrides(
            base, attention__backend="fused", attention__spike_storage="packed"
        ),
        # addition-only family (Issue 10): spike-driven k&v column sums
        # (dense + packed bit-plane decode) and token-sum QK scoring
        "sdsa-xla": with_overrides(
            base, attention__impl="sdsa", attention__backend="xla"
        ),
        "sdsa-fused-packed": with_overrides(
            base, attention__impl="sdsa", attention__backend="fused",
            attention__spike_storage="packed",
        ),
        "qksum-xla": with_overrides(
            base, attention__impl="qksum", attention__backend="xla"
        ),
    }
    b, n_ctx, pos = 4, 64, 8
    interpret = default_interpret()
    if record_path is None:
        record_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "perf_trajectory.jsonl"
        )
    params = build_model(variants["ssa-xla"]).init(jax.random.PRNGKey(0))
    records = []
    for name, cfg in variants.items():
        a = cfg.attention
        model = build_model(cfg)
        cache = model.init_cache(b, n_ctx)
        nbytes = sum(int(l.nbytes) for l in jax.tree.leaves(cache))
        batch = {
            "tokens": jnp.zeros((b, 1), jnp.int32),
            "positions": jnp.full((b, 1), pos, jnp.int32),
        }
        idx = jnp.full((b,), pos, jnp.int32)
        step = jax.jit(lambda p, bt, c, i, m=model: m.decode_step(p, bt, c, i))
        step(params, batch, cache, idx)[0].block_until_ready()
        us = _bench(
            lambda: step(params, batch, cache, idx)[0].block_until_ready(),
            iters=3, warmup=1,
        )
        storage = "packed" if a.spike_storage == "packed" else "dense"
        traffic = kv_decode_traffic(
            n_ctx, a.num_kv_heads, a.head_dim, a.ssa_time_steps, storage, 4
        )
        energy = ATTENTION_ENERGY_BY_IMPL[a.impl](
            Workload(n=n_ctx, d=a.num_heads * a.head_dim, h=a.num_heads,
                     t=a.ssa_time_steps)
        )
        rec = {
            "bench": "backend_compare",
            "backend": name,
            "decode_us": round(us, 1),
            "interpret_mode": interpret,
            "cache_bytes": nbytes,
            "modeled_bytes_moved_per_layer": traffic["bytes_moved"],
            "modeled_processing_uJ": round(energy["processing_uJ"], 4),
            "batch": b,
            "n_ctx": n_ctx,
            "ts": time.time(),
        }
        records.append(rec)
        print(
            f"backend_compare/{name},{us:.0f},"
            f"cache_bytes={nbytes};moved_B={traffic['bytes_moved']}"
            f";proc_uJ={rec['modeled_processing_uJ']}"
            f";interpret={interpret}"
        )
    records.append(_bench_vit_serving_record(interpret))
    with open(record_path, "a") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    print(f"backend_compare/records,0,appended={len(records)};path={record_path}")
    return records


def _bench_vit_serving_record(interpret: bool) -> dict:
    """One backend-compare record for the non-LM workload: spiking-ViT
    event streams classified through the paged serving engine (prefill-only,
    ``max_new_tokens=1`` — zero decode ticks by construction)."""
    import jax
    import numpy as np

    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    from .energy_model import ATTENTION_ENERGY_BY_IMPL, Workload, kv_decode_traffic

    cfg = with_overrides(
        get_smoke_config("spiking_vit_small"), attention__cache_layout="paged"
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_patches, b = model.num_patches, 2
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, model.num_events, n_patches).astype(np.int32)
        for _ in range(b)
    ]

    def classify():
        eng = ServingEngine(model, params, num_slots=b, max_seq=n_patches,
                            page_size=16)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p.copy(), max_new_tokens=1,
                               seed=i + 1))
        done = eng.run_until_done(max_ticks=10)
        assert len(done) == b and eng.steps_run == 0
        return eng

    eng = classify()                       # warm the jit caches
    us = _bench(classify, iters=3, warmup=0) / b   # per classification
    a = cfg.attention
    traffic = kv_decode_traffic(
        n_patches, a.num_kv_heads, a.head_dim, a.ssa_time_steps, "dense", 4
    )
    energy = ATTENTION_ENERGY_BY_IMPL[a.impl](
        Workload(n=n_patches, d=a.num_heads * a.head_dim, h=a.num_heads,
                 t=a.ssa_time_steps)
    )
    rec = {
        "bench": "backend_compare",
        "backend": "vit-ssa-event-stream",
        "decode_us": round(us, 1),         # per-image admission->class time
        "interpret_mode": interpret,
        "cache_bytes": eng.kv_cache_nbytes(),
        "modeled_bytes_moved_per_layer": traffic["bytes_moved"],
        "modeled_processing_uJ": round(energy["processing_uJ"], 4),
        "batch": b,
        "n_ctx": n_patches,
        "ts": time.time(),
    }
    print(
        f"backend_compare/vit-ssa-event-stream,{us:.0f},"
        f"cache_bytes={rec['cache_bytes']}"
        f";moved_B={rec['modeled_bytes_moved_per_layer']}"
        f";proc_uJ={rec['modeled_processing_uJ']}"
        f";prefill_only=True;interpret={interpret}"
    )
    return rec


def bench_paging_compare(record_path: str | None = None):
    """Slab vs paged serving on a synthetic bursty trace (smoke SSA model,
    packed storage, CPU).

    Both engines serve the identical trace; the paged engine is configured
    with the same page-pool bytes as the slab engine's whole cache
    (``slab_slots * pages_per_seq`` usable pages) but twice the decode rows,
    so short-prompt bursts can actually use the memory: the comparison
    reports kv bytes allocated, achieved concurrency, tokens/sec and
    preemption counters, and writes ``benchmarks/BENCH_paging.json``.
    """
    import jax
    import numpy as np

    from repro.attention import NUM_RESERVED_PAGES
    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    slab_slots, paged_slots, max_seq, page_size = 4, 8, 64, 16
    base = with_overrides(
        get_smoke_config("codeqwen15_7b"),
        attention__impl="ssa",
        attention__spike_storage="packed",
    )
    variants = {
        "slab": (base, {}),
        "paged": (
            with_overrides(base, attention__cache_layout="paged"),
            {
                "page_size": page_size,
                # same usable pool bytes as the slab engine's 4 slots
                "num_pages": NUM_RESERVED_PAGES
                + slab_slots * (max_seq // page_size),
            },
        ),
    }

    # bursty synthetic trace: 3 waves of short-prompt requests
    rng = np.random.default_rng(0)
    def trace():
        reqs, arrivals = [], []
        uid = 0
        for wave, tick in enumerate((0, 4, 8)):
            for _ in range(6):
                reqs.append(
                    Request(
                        uid=uid,
                        prompt=rng.integers(
                            0, base.vocab_size, int(rng.integers(3, 12))
                        ).astype(np.int32),
                        max_new_tokens=int(rng.integers(4, 10)),
                    )
                )
                arrivals.append(tick)
                uid += 1
        return reqs, arrivals

    params = build_model(base).init(jax.random.PRNGKey(0))
    if record_path is None:
        record_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_paging.json"
        )
    results = {}
    for name, (cfg, kw) in variants.items():
        rng = np.random.default_rng(0)  # same trace per engine
        model = build_model(cfg)
        slots = slab_slots if name == "slab" else paged_slots
        # always traced: event totals are deterministic scheduler outputs
        # the regression gate diffs against the committed baseline
        tracer = _make_tracer(always=True)
        eng = ServingEngine(
            model, params, num_slots=slots, max_seq=max_seq,
            tracer=tracer, **kw
        )
        reqs, arrivals = trace()
        t0 = time.perf_counter()
        done, tick, i = [], 0, 0
        max_active = 0
        while i < len(reqs) or eng.has_pending_work:
            while i < len(reqs) and arrivals[i] <= tick:
                eng.submit(reqs[i])
                i += 1
            done.extend(eng.step())
            max_active = max(max_active, len(eng.active))
            tick += 1
            assert tick < 2000
        wall = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        stats = eng.stats()
        results[name] = {
            "kv_bytes_allocated": eng.kv_cache_nbytes(),
            "decode_rows": slots,
            "achieved_concurrency": (
                stats.get("max_concurrency_seen") or max_active
            ),
            "requests": len(done),
            "tokens": toks,
            "ticks": tick,
            "tokens_per_sec": round(toks / wall, 1),
            "preemptions": stats.get("preemptions", 0),
            "queue_wait_ticks": stats.get("queue_wait_ticks", 0),
            "events": _event_totals(tracer),
        }
        _export_trace(tracer, f"paging_{name}")
        r = results[name]
        print(
            f"paging_compare/{name},{wall * 1e6 / max(toks, 1):.0f},"
            f"kv_bytes={r['kv_bytes_allocated']}"
            f";concurrency={r['achieved_concurrency']}"
            f";ticks={r['ticks']};tok_s={r['tokens_per_sec']}"
            f";preemptions={r['preemptions']}"
        )
    rec = {
        "bench": "paging_compare",
        "trace": {"requests": 18, "waves": 3, "max_seq": max_seq},
        "page_size": page_size,
        "engines": results,
        "concurrency_gain": round(
            results["paged"]["achieved_concurrency"]
            / max(results["slab"]["achieved_concurrency"], 1), 2
        ),
        "kv_bytes_ratio": round(
            results["paged"]["kv_bytes_allocated"]
            / max(results["slab"]["kv_bytes_allocated"], 1), 3
        ),
        "ts": time.time(),
    }
    with open(record_path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    _append_trajectory(rec)
    print(
        f"paging_compare/summary,0,concurrency_gain={rec['concurrency_gain']}"
        f";kv_bytes_ratio={rec['kv_bytes_ratio']};path={record_path}"
    )
    return rec


def bench_prefill_compare(record_path: str | None = None):
    """Chunked vs one-shot paged prefill on an over-long-prompt workload
    (smoke SSA model, packed storage + paged cache, CPU).

    One 48-token prompt — six pages, far wider than any chunk — served by a
    fresh engine per variant (cold jit caches, fresh model instances so the
    per-model compile memo cannot leak between variants).  The comparison
    reports **peak prefill staging bytes** (the one-shot path materialises
    an O(max_seq) slab row cache per admission and scatters it; the chunked
    path writes O(chunk) tokens straight into pool pages) and **admission
    latency** cold and warm (submit -> first sampled token), then verifies
    the two streams are bit-identical and writes
    ``benchmarks/BENCH_prefill.json``.  The memory ratios are the headline;
    the latency columns are honesty checks — at smoke scale on CPU the
    chunked path's N small dispatches cost more wall time than one big
    dispatch, the deliberate trade for O(chunk) staging and per-chunk page
    claims.
    """
    import jax
    import numpy as np

    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    max_seq, page_size, prompt_len, chunk = 64, 8, 48, 8
    cfg = with_overrides(
        get_smoke_config("codeqwen15_7b"),
        attention__impl="ssa",
        attention__spike_storage="packed",
        attention__cache_layout="paged",
    )
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    warm_prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)

    def staging_bytes(eng) -> int:
        """Bytes of per-admission staging state outside the shared pool."""
        if eng.prefill_chunk:
            # chunked: no slab staging row; the transient is one chunk of
            # tokens/positions (the written K/V lands in the pool in place)
            return int(2 * chunk * np.dtype(np.int32).itemsize)
        return sum(int(l.nbytes) for l in jax.tree.leaves(eng._init_row))

    def compiled_temp_bytes(eng, model, params):
        """XLA temp allocation of the compiled prefill computation (None if
        this backend exposes no memory analysis)."""
        try:
            if eng.prefill_chunk:
                from repro.attention import bucketed_table_width

                cache = model.init_cache(
                    1, max_seq, layout="paged",
                    num_pages=eng.pool.num_pages, page_size=page_size,
                )
                # lower the PEAK chunk signature (the widest block table
                # the engine compiles for this prompt), not the cheapest
                width = bucketed_table_width(
                    prompt_len, page_size, max_seq // page_size
                )
                cache = [
                    {k: (v[:, :1, :width] if k == "bt" else v)
                     for k, v in d.items()}
                    for d in cache
                ]
                f = jax.jit(lambda p, b, c, i, s: model.decode_step(
                    p, b, c, i, seeds=s, logits_at=jnp_scalar(chunk - 1)))
                import jax.numpy as jnp
                lowered = f.lower(
                    params,
                    {"tokens": jnp.zeros((1, chunk), jnp.int32),
                     "positions": jnp.zeros((1, chunk), jnp.int32)},
                    cache, jnp.zeros((1,), jnp.int32),
                    jnp.zeros((1,), jnp.uint32),
                )
            else:
                import jax.numpy as jnp
                f = jax.jit(lambda p, b, c, s: model.prefill(
                    p, b, c, logits_at=jnp_scalar(prompt_len - 1), seeds=s))
                lowered = f.lower(
                    params,
                    {"tokens": jnp.zeros((1, max_seq), jnp.int32),
                     "positions": jnp.zeros((1, max_seq), jnp.int32)},
                    model.init_cache(1, max_seq),
                    jnp.zeros((1,), jnp.uint32),
                )
            ma = lowered.compile().memory_analysis()
            return int(ma.temp_size_in_bytes) if ma is not None else None
        except Exception:
            return None

    def jnp_scalar(v):
        import jax.numpy as jnp

        return jnp.asarray(v, jnp.int32)

    if record_path is None:
        record_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_prefill.json"
        )
    results, streams = {}, {}
    for name, pc in (("one_shot", 0), ("chunked", chunk)):
        model = build_model(cfg)          # fresh instance: cold jit memo
        params = model.init(jax.random.PRNGKey(0))
        tracer = _make_tracer()
        eng = ServingEngine(
            model, params, num_slots=1, max_seq=max_seq,
            page_size=page_size, prefill_chunk=pc, tracer=tracer,
        )
        def first_token_latency(uid, toks):
            req = Request(uid=uid, prompt=toks, max_new_tokens=4)
            t0 = time.perf_counter()
            eng.submit(req)
            while not req.out_tokens and eng.has_pending_work:
                eng.step()
            dt = time.perf_counter() - t0
            eng.run_until_done(max_ticks=50)
            return req, dt

        req, t_cold = first_token_latency(0, prompt.copy())
        # warm path: same length, different tokens — compiles are cached,
        # this is the steady-state admission cost (min of 3 to cut noise).
        # At smoke scale the chunked path is expected to be SLOWER here:
        # it pays N small dispatches + host-side table builds where the
        # one-shot path pays one big dispatch — the trade it makes for
        # O(chunk) staging memory and per-chunk page claims.
        t_warm = min(
            first_token_latency(1 + i, warm_prompt.copy())[1]
            for i in range(3)
        )
        streams[name] = list(req.out_tokens)
        st = eng.stats()
        results[name] = {
            "prefill_chunk": pc,
            "staging_bytes": staging_bytes(eng),
            "compiled_temp_bytes": compiled_temp_bytes(eng, model, params),
            "admission_latency_cold_s": round(t_cold, 4),
            "admission_latency_warm_s": round(t_warm, 4),
            "prefill_chunks_run": st["prefill_chunks_run"],
            "chunk_signatures": len(eng._chunk_signatures),
        }
        _export_trace(tracer, f"prefill_{name}")
        r = results[name]
        print(
            f"prefill_compare/{name},{t_warm * 1e6:.0f},"
            f"staging_bytes={r['staging_bytes']}"
            f";temp_bytes={r['compiled_temp_bytes']}"
            f";cold_s={r['admission_latency_cold_s']}"
            f";chunks={r['prefill_chunks_run']}"
        )
    assert streams["one_shot"] == streams["chunked"], "stream identity broke"
    rec = {
        "bench": "prefill_compare",
        "workload": {"prompt_len": prompt_len, "max_seq": max_seq,
                     "page_size": page_size, "chunk": chunk},
        "engines": results,
        "streams_identical": True,
        "staging_bytes_ratio": round(
            results["one_shot"]["staging_bytes"]
            / max(results["chunked"]["staging_bytes"], 1), 1
        ),
        "admission_latency_cold_ratio": round(
            results["one_shot"]["admission_latency_cold_s"]
            / max(results["chunked"]["admission_latency_cold_s"], 1e-9), 2
        ),
        "admission_latency_warm_ratio": round(
            results["one_shot"]["admission_latency_warm_s"]
            / max(results["chunked"]["admission_latency_warm_s"], 1e-9), 2
        ),
        "ts": time.time(),
    }
    with open(record_path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    _append_trajectory(rec)
    print(
        f"prefill_compare/summary,0,"
        f"staging_ratio={rec['staging_bytes_ratio']}"
        f";warm_latency_ratio={rec['admission_latency_warm_ratio']}"
        f";identical={rec['streams_identical']};path={record_path}"
    )
    return rec


def bench_sharing_compare(record_path: str | None = None):
    """Prefix sharing and the persistent prefix cache over one bursty
    multi-tenant trace (smoke SSA model, packed storage + paged cache,
    CPU).

    Four tenants each pin a distinct 16-token system prompt; request
    popularity is Zipf-skewed across tenants (the hot tenant dominates)
    and requests arrive in waves separated by idle gaps long enough for
    every wave to drain — the shape where plain live-owner sharing buys
    nothing *across* waves because the last owner's pages are scrubbed on
    release.  Three engines serve the identical trace from identical
    pools:

    * ``unshared`` — every request prefills its own pages;
    * ``shared``   — live CoW prefix sharing only (skips chunks within a
      wave, re-prefills every wave);
    * ``cached``   — sharing plus a persistent cache tier that parks
      refcount-0 prefix pages between waves, so later waves revive hot
      tenants' pages instead of re-running their prefill chunks.

    Greedy token streams are asserted bit-identical across all three.
    The record (``benchmarks/BENCH_sharing.json``) carries the prefill
    dispatch counts, cache hit/miss/eviction counters, the cache hit rate
    and the headline ``prefill_dispatch_reduction`` of cached vs shared,
    plus per-engine trace-event totals for the regression gate.
    """
    import jax
    import numpy as np

    from repro.attention import NUM_RESERVED_PAGES
    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    slots, max_seq, page_size = 6, 64, 8
    num_pages = NUM_RESERVED_PAGES + 14   # tight: forces queueing unshared
    cache_pages = 6   # < 4 tenants * 2 prefix pages: cold tenants evict
    n_tenants, waves, per_wave = 4, 3, 6
    cfg = with_overrides(
        get_smoke_config("codeqwen15_7b"),
        attention__impl="ssa",
        attention__spike_storage="packed",
        attention__cache_layout="paged",
    )

    def trace():
        rng = np.random.default_rng(0)
        systems = [
            rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
            for _ in range(n_tenants)
        ]
        # Zipf popularity over tenants: p(rank) ~ 1 / rank^1.2
        p = 1.0 / np.arange(1, n_tenants + 1) ** 1.2
        p /= p.sum()
        burst, uid = [], 0
        for _ in range(waves):
            wave = []
            for _ in range(per_wave):
                tenant = int(rng.choice(n_tenants, p=p))
                suffix = rng.integers(
                    0, cfg.vocab_size, int(rng.integers(3, 9))
                ).astype(np.int32)
                wave.append(
                    Request(
                        uid=uid,
                        prompt=np.concatenate([systems[tenant], suffix]),
                        max_new_tokens=int(rng.integers(4, 10)),
                    )
                )
                uid += 1
            burst.append(wave)
        return burst

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if record_path is None:
        record_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_sharing.json"
        )
    variants = (
        ("unshared", dict(share_prefix=False)),
        ("shared", dict(share_prefix=True)),
        ("cached", dict(share_prefix=True, prefix_cache_pages=cache_pages)),
    )
    results, streams = {}, {}
    for name, kw in variants:
        tracer = _make_tracer(always=True)
        eng = ServingEngine(
            model, params, num_slots=slots, max_seq=max_seq,
            page_size=page_size, num_pages=num_pages, tracer=tracer, **kw,
        )
        burst = trace()
        t0 = time.perf_counter()
        done, tick = [], 0
        for wave in burst:
            for req in wave:
                eng.submit(req)
            # idle gap until the wave drains: the persistent-cache case
            while eng.has_pending_work:
                done.extend(eng.step())
                tick += 1
                assert tick < 2000
        wall = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        stats = eng.stats()
        streams[name] = {
            r.uid: [int(t) for t in r.out_tokens] for r in done
        }
        results[name] = {
            "requests": len(done),
            "tokens": toks,
            "ticks": tick,
            "tokens_per_sec": round(toks / wall, 1),
            "peak_pages_used": stats["peak_pages_used"],
            "achieved_concurrency": stats["max_concurrency_seen"],
            "queue_wait_ticks": stats["queue_wait_ticks"],
            "preemptions": stats["preemptions"],
            "shared_page_hits": stats["shared_page_hits"],
            "cow_copies": stats["cow_copies"],
            "prefill_chunks_run": stats["prefill_chunks_run"],
            "prefill_chunks_skipped": stats["prefill_chunks_skipped"],
            "cache_inserts": stats.get("cache_inserts", 0),
            "cache_hits": stats.get("cache_hits", 0),
            "cache_misses": stats.get("cache_misses", 0),
            "cache_evictions": stats.get("cache_evictions", 0),
            "cached_pages_now": stats.get("cached_pages_now", 0),
            "events": _event_totals(tracer),
        }
        _export_trace(tracer, f"sharing_{name}")
        r = results[name]
        print(
            f"sharing_compare/{name},{wall * 1e6 / max(toks, 1):.0f},"
            f"peak_pages={r['peak_pages_used']}"
            f";queue_wait={r['queue_wait_ticks']}"
            f";chunks_run={r['prefill_chunks_run']}"
            f";chunks_skipped={r['prefill_chunks_skipped']}"
            f";hits={r['shared_page_hits']};cow={r['cow_copies']}"
            f";cache_hits={r['cache_hits']}"
            f";cache_evictions={r['cache_evictions']}"
        )
    assert streams["unshared"] == streams["shared"] == streams["cached"], (
        "greedy streams must be bit-identical across sharing/cache variants"
    )
    cached = results["cached"]
    lookups = cached["cache_hits"] + cached["cache_misses"]
    rec = {
        "bench": "sharing_compare",
        "trace": {"requests": waves * per_wave, "waves": waves,
                  "tenants": n_tenants, "zipf_s": 1.2,
                  "system_prompt_tokens": 16},
        "pool": {"num_pages": num_pages, "page_size": page_size,
                 "slots": slots, "max_seq": max_seq,
                 "cache_pages": cache_pages},
        "engines": results,
        "streams_identical": True,
        "page_savings": round(
            1.0 - results["shared"]["peak_pages_used"]
            / max(results["unshared"]["peak_pages_used"], 1), 3
        ),
        "cache_hit_rate": round(cached["cache_hits"] / max(lookups, 1), 3),
        "prefill_dispatch_reduction": round(
            1.0 - cached["prefill_chunks_run"]
            / max(results["shared"]["prefill_chunks_run"], 1), 3
        ),
        "ts": time.time(),
    }
    with open(record_path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    _append_trajectory(rec)
    print(
        f"sharing_compare/summary,0,page_savings={rec['page_savings']}"
        f";cache_hit_rate={rec['cache_hit_rate']}"
        f";prefill_dispatch_reduction={rec['prefill_dispatch_reduction']}"
        f";path={record_path}"
    )
    return rec


def bench_spec_compare(record_path: str | None = None):
    """Self-speculative vs plain greedy decode on one pinned workload
    (smoke SSA model, packed storage + paged cache, CPU).

    The target runs SSA at T=8; the draft is the same weights at T=4
    (half the Bernoulli rounds per token, so roughly half the decode
    cost) proposing ``k=4`` tokens per tick.  A single decode row keeps
    the headline metric honest: for the plain engine every committed
    token past a request's first (which prefill samples) costs exactly
    one target dispatch, so the speculative engine's
    ``verify_dispatches / tokens`` reads directly against the plain
    engine's ``ticks / tokens``.  Acceptance statistics are
    deterministic (pinned request seeds, greedy sampling, RNG contract
    v2), streams must match token-for-token, and the record lands in
    ``benchmarks/BENCH_spec.json`` + the perf trajectory.
    """
    import jax
    import numpy as np

    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model
    from repro.serving import DraftConfig, Request, ServingEngine

    max_seq, page_size, spec_k = 64, 8, 4
    cfg = with_overrides(
        get_smoke_config("codeqwen15_7b"),
        attention__impl="ssa",
        attention__spike_storage="packed",
        attention__cache_layout="paged",
        attention__ssa_time_steps=8,      # target precision: T=8
    )

    def trace():
        rng = np.random.default_rng(0)
        reqs = []
        for uid in range(4):
            reqs.append(
                Request(
                    uid=uid,
                    prompt=rng.integers(
                        0, cfg.vocab_size, int(rng.integers(4, 12))
                    ).astype(np.int32),
                    max_new_tokens=12,
                    seed=uid * 7 + 1,
                )
            )
        return reqs

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if record_path is None:
        record_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_spec.json"
        )
    variants = {
        "plain": None,
        "speculative": DraftConfig(k=spec_k, time_steps=4),
    }
    results, streams = {}, {}
    for name, draft in variants.items():
        tracer = _make_tracer(always=True)
        eng = ServingEngine(
            model, params, num_slots=1, max_seq=max_seq,
            page_size=page_size, draft=draft, tracer=tracer,
        )
        reqs = trace()
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        done = eng.run_until_done(max_ticks=500)
        wall = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        streams[name] = [list(r.out_tokens) for r in reqs]
        stats = eng.stats()
        hist = eng.metrics.snapshot()["histograms"].get("accepted_len")
        drafted = stats.get("spec_drafted_tokens", 0)
        accepted = stats.get("spec_accepted_tokens", 0)
        # "ticks" counts decode dispatches only (prefill chunks are not
        # ticks), so for the plain engine it IS the target dispatch count
        target_dispatches = (
            stats.get("verify_dispatches", 0) if draft is not None
            else stats["ticks"]
        )
        results[name] = {
            "requests": len(done),
            "tokens": toks,
            "ticks": stats["ticks"],
            "tokens_per_sec": round(toks / wall, 1),
            "target_dispatches": target_dispatches,
            "dispatches_per_token": round(
                target_dispatches / max(toks, 1), 4
            ),
            "draft_dispatches": stats.get("draft_dispatches", 0),
            "drafted_tokens": drafted,
            "accepted_tokens": accepted,
            "accept_rate": round(accepted / drafted, 4) if drafted else None,
            "accepted_len_hist": (
                {"count": hist["count"], "sum": hist["sum"],
                 "mean": round(hist["mean"], 4), "max": hist["max"]}
                if hist else None
            ),
            "events": _event_totals(tracer),
        }
        _export_trace(tracer, f"spec_{name}")
        r = results[name]
        print(
            f"spec_compare/{name},{wall * 1e6 / max(toks, 1):.0f},"
            f"dispatches_per_token={r['dispatches_per_token']}"
            f";accept_rate={r['accept_rate']}"
            f";ticks={r['ticks']};tok_s={r['tokens_per_sec']}"
        )
    assert streams["plain"] == streams["speculative"], (
        "speculative greedy stream diverged from plain decode"
    )
    rec = {
        "bench": "spec_compare",
        "workload": {"requests": 4, "max_new_tokens": 12,
                     "max_seq": max_seq, "page_size": page_size},
        "target_time_steps": 8,
        "draft_time_steps": 4,
        "spec_k": spec_k,
        "engines": results,
        "streams_identical": True,
        "dispatch_savings": round(
            1.0 - results["speculative"]["dispatches_per_token"]
            / max(results["plain"]["dispatches_per_token"], 1e-9), 4
        ),
        "ts": time.time(),
    }
    with open(record_path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    _append_trajectory(rec)
    print(
        f"spec_compare/summary,0,"
        f"dispatch_savings={rec['dispatch_savings']}"
        f";identical={rec['streams_identical']};path={record_path}"
    )
    return rec


def bench_sharded_compare(record_path: str | None = None):
    """Tensor-parallel shards x data-parallel replicas over one pinned
    bursty workload (smoke SSA model, packed storage + paged cache, CPU).

    Five configurations serve the identical 12-request trace with the
    same *total* usable pool pages (replicated engines split the pool:
    two replicas each get half) and the same per-engine decode rows:

    * ``s1r1`` — the plain single-engine baseline;
    * ``s2r1`` / ``s4r1`` — one engine, KV heads sharded 2- / 4-way over
      a device mesh (per-shard bytes shrink; scheduling is unchanged);
    * ``s1r2`` / ``s2r2`` — two replicas behind one admission queue
      (each optionally 2-way sharded), doubling joint decode rows on the
      same total pool.

    Every draw is keyed by request seed and absolute position (RNG
    contract v2), and TP collectives are pure data movement, so all five
    greedy streams must be **bit-identical** — asserted, then recorded
    with achieved concurrency, queue-wait ticks, and per-replica
    dispatch counts in ``benchmarks/BENCH_sharded.json``.  The headline
    is ``concurrency_gain_2_replicas`` (>= 1.5x on this trace).
    """
    import jax
    import numpy as np

    from repro.attention import NUM_RESERVED_PAGES
    from repro.configs import get_smoke_config, with_overrides
    from repro.models import build_model
    from repro.serving import ReplicatedEngine, Request, ServingEngine

    if len(jax.devices()) < 4:
        raise SystemExit(
            f"sharded compare needs >= 4 devices, found {len(jax.devices())}"
            "; on CPU run with XLA_FLAGS=--xla_force_host_platform_"
            "device_count=8 JAX_PLATFORMS=cpu"
        )

    slots, max_seq, page_size, usable = 4, 32, 8, 16
    cfg = with_overrides(
        get_smoke_config("codeqwen15_7b"),
        attention__impl="ssa",
        attention__spike_storage="packed",
        attention__cache_layout="paged",
    )

    def trace():
        # 12 short requests in two waves; pinned seeds make every stream
        # placement-invariant (prompt+new <= 15 tokens -> <= 2 pages/row)
        rng = np.random.default_rng(0)
        reqs, arrivals = [], []
        for uid in range(12):
            reqs.append(
                Request(
                    uid=uid,
                    prompt=rng.integers(
                        0, cfg.vocab_size, int(rng.integers(4, 9))
                    ).astype(np.int32),
                    max_new_tokens=int(rng.integers(4, 8)),
                    seed=uid * 11 + 3,
                )
            )
            arrivals.append(0 if uid < 8 else 3)
        return reqs, arrivals

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if record_path is None:
        record_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_sharded.json"
        )
    configs = (
        ("s1r1", 1, 1),
        ("s2r1", 2, 1),
        ("s4r1", 4, 1),
        ("s1r2", 1, 2),
        ("s2r2", 2, 2),
    )
    results, streams = {}, {}
    for name, shards, replicas in configs:
        tracer = _make_tracer(always=True)
        kw = dict(
            num_slots=slots, max_seq=max_seq, page_size=page_size,
            # same total usable pool: each replica owns its slice
            num_pages=NUM_RESERVED_PAGES + usable // replicas,
            tracer=tracer,
        )
        if shards > 1:
            kw["mesh_shards"] = shards
        if replicas > 1:
            eng = ReplicatedEngine(model, params, replicas=replicas, **kw)
        else:
            eng = ServingEngine(model, params, **kw)
        reqs, arrivals = trace()
        t0 = time.perf_counter()
        done, tick, i = [], 0, 0
        while i < len(reqs) or eng.has_pending_work:
            while i < len(reqs) and arrivals[i] <= tick:
                eng.submit(reqs[i])
                i += 1
            done.extend(eng.step())
            tick += 1
            assert tick < 2000
        wall = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        stats = eng.stats()
        streams[name] = {
            r.uid: [int(t) for t in r.out_tokens] for r in done
        }
        results[name] = {
            "mesh_shards": shards,
            "replicas": replicas,
            "usable_pages_per_replica": usable // replicas,
            "kv_bytes_total": eng.kv_cache_nbytes(),
            "kv_shard_nbytes": (
                eng.kv_shard_nbytes() if shards > 1 and replicas == 1
                else [e.kv_shard_nbytes() for e in eng.engines]
                if shards > 1 else None
            ),
            "dispatched": (
                eng.request_counts() if replicas > 1 else [len(done)]
            ),
            "achieved_concurrency": (
                eng.max_concurrency_seen if replicas > 1
                else stats["max_concurrency_seen"]
            ),
            "requests": len(done),
            "tokens": toks,
            "ticks": tick,
            "tokens_per_sec": round(toks / wall, 1),
            "queue_wait_ticks": stats["queue_wait_ticks"],
            "preemptions": (
                sum(s["preemptions"] for s in stats["per_replica"])
                if replicas > 1 else stats["preemptions"]
            ),
            "events": _event_totals(tracer),
        }
        _export_trace(tracer, f"sharded_{name}")
        r = results[name]
        print(
            f"sharded_compare/{name},{wall * 1e6 / max(toks, 1):.0f},"
            f"concurrency={r['achieved_concurrency']}"
            f";queue_wait={r['queue_wait_ticks']}"
            f";dispatched={'/'.join(map(str, r['dispatched']))}"
            f";kv_bytes={r['kv_bytes_total']};tok_s={r['tokens_per_sec']}"
        )
    base = streams["s1r1"]
    for name, got in streams.items():
        assert got == base, (
            f"{name} greedy streams diverged from the unsharded baseline"
        )
    gain = round(
        results["s1r2"]["achieved_concurrency"]
        / max(results["s1r1"]["achieved_concurrency"], 1), 2
    )
    assert gain >= 1.5, (
        f"2-replica concurrency gain {gain} < 1.5x on the same total pool"
    )
    rec = {
        "bench": "sharded_compare",
        "workload": {"requests": 12, "waves": 2, "max_seq": max_seq},
        "pool": {"usable_pages_total": usable, "page_size": page_size,
                 "slots_per_engine": slots},
        "devices": len(jax.devices()),
        "engines": results,
        "streams_identical": True,
        "concurrency_gain_2_replicas": gain,
        "ts": time.time(),
    }
    with open(record_path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    _append_trajectory(rec)
    print(
        f"sharded_compare/summary,0,streams_identical=True"
        f";concurrency_gain_2_replicas={gain};path={record_path}"
    )
    return rec


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--compare-storage",
        action="store_true",
        help="only run the dense-vs-packed spike-storage comparison",
    )
    parser.add_argument(
        "--compare-backends",
        action="store_true",
        help="only run the attention-backend decode comparison "
        "(appends to benchmarks/perf_trajectory.jsonl)",
    )
    parser.add_argument(
        "--compare-paging",
        action="store_true",
        help="only run the slab-vs-paged serving comparison "
        "(writes benchmarks/BENCH_paging.json)",
    )
    parser.add_argument(
        "--compare-sharing",
        action="store_true",
        help="only run the prefix-sharing on/off serving comparison "
        "(writes benchmarks/BENCH_sharing.json)",
    )
    parser.add_argument(
        "--compare-prefill",
        action="store_true",
        help="only run the chunked vs one-shot paged-prefill comparison "
        "(writes benchmarks/BENCH_prefill.json)",
    )
    parser.add_argument(
        "--compare-spec",
        action="store_true",
        help="only run the speculative vs plain greedy-decode comparison "
        "(writes benchmarks/BENCH_spec.json)",
    )
    parser.add_argument(
        "--compare-sharded",
        action="store_true",
        help="only run the sharded/replicated serving comparison "
        "(writes benchmarks/BENCH_sharded.json; needs >= 4 devices)",
    )
    parser.add_argument(
        "--trace-out",
        metavar="PATH",
        default=None,
        help="export one Perfetto/Chrome-trace JSON per engine run to "
        "PATH-stem.<bench>_<engine>.json (serving compare modes)",
    )
    args = parser.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    global _TRACE_OUT
    _TRACE_OUT = args.trace_out
    if args.compare_storage:
        bench_storage_compare()
        return
    if args.compare_backends:
        bench_backend_compare()
        return
    if args.compare_paging:
        bench_paging_compare()
        return
    if args.compare_sharing:
        bench_sharing_compare()
        return
    if args.compare_prefill:
        bench_prefill_compare()
        return
    if args.compare_spec:
        bench_spec_compare()
        return
    if args.compare_sharded:
        bench_sharded_compare()
        return
    bench_table2_energy()
    bench_table3_latency()
    bench_ssa_kernel()
    bench_roofline_summary()
    bench_storage_compare()
    bench_table1_accuracy()


if __name__ == "__main__":
    main()
