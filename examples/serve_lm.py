"""Serving demo: continuous batching with the slot-based engine.

Trains nothing — loads a smoke-size LM with random weights (or a checkpoint
from `launch.train`) and pushes a burst of variable-length requests through
the decode loop, demonstrating slot reuse, per-slot cache offsets and EOS
handling.  With ``--cache-layout paged`` the KV cache is a shared page pool
(``--num-pages`` sizes it; see docs/serving.md): undersize it and the
scheduler preempts and resumes requests — greedy token streams stay
identical to the slab engine either way.

With ``--share-prefix`` (paged layout) every demo request gets a shared
16-token system prompt and the engine maps its full pages once, copy-on-
write — the printed stats show physical-page hits and CoW copies.

Run:  PYTHONPATH=src python examples/serve_lm.py [--arch codeqwen15_7b]
      PYTHONPATH=src python examples/serve_lm.py --impl ssa --spike-storage packed
      PYTHONPATH=src python examples/serve_lm.py --impl ssa --backend fused \
          --spike-storage packed --temperature 0.8 --top-k 40 --top-p 0.95
      PYTHONPATH=src python examples/serve_lm.py --impl ssa --spike-storage packed \
          --cache-layout paged --page-size 16 --num-pages 14
      PYTHONPATH=src python examples/serve_lm.py --impl ssa --spike-storage packed \
          --cache-layout paged --share-prefix
      PYTHONPATH=src python examples/serve_lm.py --impl ssa --spike-storage packed \
          --cache-layout paged --prefill-chunk 16
      PYTHONPATH=src python examples/serve_lm.py --impl ssa --spike-storage packed \
          --cache-layout paged --draft-k 4
      XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
          PYTHONPATH=src python examples/serve_lm.py --impl ssa \
          --spike-storage packed --cache-layout paged --mesh-shards 2 --replicas 2

``--mesh-shards N`` shards the KV-cache heads N ways over a device mesh
(tensor parallelism; needs N devices — force them on CPU with the
``XLA_FLAGS`` shown above) and ``--replicas N`` runs N engines behind one
least-loaded admission queue (data parallelism); token streams stay
bit-identical either way, and the final stats add per-shard pool bytes
and per-replica request counts.

Paged engines prefill in page-aligned chunks written straight into pool
pages by default (``--prefill-chunk 0`` restores the one-shot slab-staged
prefill; streams are bit-identical either way).

With ``--trace-out trace.json`` the run is traced (token streams stay
bit-identical) and exported as Perfetto/Chrome-trace JSON — load it at
``ui.perfetto.dev`` to see tick phases and per-request lifelines.
``--trace-events N`` prints the last N trace-event signatures, and any
traced run prints TTFT / inter-token latency percentiles from the
engine's metrics registry (see docs/observability.md).
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_smoke_config, with_overrides
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.obs import Tracer, export_perfetto
from repro.serving import (
    DraftConfig,
    ReplicatedEngine,
    Request,
    ServingEngine,
    make_sampler,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen15_7b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--impl", default=None,
                    choices=["ann", "ssa", "spikformer", "sdsa", "qksum"],
                    help="override the attention implementation (sdsa/qksum "
                         "= the addition-only spiking families)")
    ap.add_argument("--spike-storage", default=None, choices=["dense", "packed"],
                    help="KV-cache spike storage (packed = uint32 bit-planes; "
                         "ssa/sdsa impls only)")
    ap.add_argument("--backend", default=None, choices=["auto", "xla", "fused"],
                    help="attention backend (fused = Pallas kernels; "
                         "interpret-mode and slow on CPU)")
    ap.add_argument("--temperature", type=float, default=None,
                    help="sample with this temperature instead of greedy argmax")
    ap.add_argument("--top-k", type=int, default=None,
                    help="restrict sampling to the k highest logits")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus sampling: keep the smallest top-p "
                         "probability mass")
    ap.add_argument("--cache-layout", default=None, choices=["slab", "paged"],
                    help="KV-cache layout (paged = shared page pool with "
                         "preemption; see docs/serving.md)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="rows per page (paged layout; must divide max-seq)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged layout: prefill chunk size in tokens "
                         "(page-aligned; default one page per chunk, 0 = "
                         "one-shot slab-staged prefill)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="total pool pages incl. 2 reserved (paged layout; "
                         "default fits slots*max_seq)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="map requests with a common prompt prefix onto the "
                         "same physical pages (copy-on-write; paged layout "
                         "only — the demo gives every request a shared "
                         "system prompt so the sharing is visible)")
    ap.add_argument("--prefix-cache-pages", type=int, default=0, metavar="N",
                    help="persistent prefix cache: park up to N refcount-0 "
                         "shared pages unscrubbed when their last owner "
                         "drains, so later requests with the same prefix "
                         "skip the prefill (0 = off; requires "
                         "--share-prefix — the demo submits in two waves "
                         "so the revival is visible)")
    ap.add_argument("--draft-k", type=int, default=None, metavar="K",
                    help="self-speculative decode: propose up to K tokens "
                         "per tick with a cheap draft, verify with one "
                         "target prefix-extend (paged layout; greedy "
                         "streams stay exact — see docs/serving.md)")
    ap.add_argument("--draft-time-steps", type=int, default=None,
                    help="SSA time steps for the draft model (default "
                         "half the target's; ignored without --draft-k)")
    ap.add_argument("--mesh-shards", type=int, default=1, metavar="N",
                    help="shard KV-cache heads N ways over a device mesh "
                         "(tensor parallelism; needs N devices — on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8; streams stay bit-identical)")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="run N engine replicas behind one least-loaded "
                         "admission queue (data parallelism; every engine "
                         "kwarg, --num-pages included, is per replica)")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="trace the run and export Perfetto/Chrome-trace "
                         "JSON to PATH (open at ui.perfetto.dev)")
    ap.add_argument("--trace-events", type=int, default=0, metavar="N",
                    help="trace the run and print the last N event "
                         "signatures")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    if args.impl:
        cfg = with_overrides(cfg, attention__impl=args.impl)
    if args.spike_storage:
        cfg = with_overrides(cfg, attention__spike_storage=args.spike_storage)
    if args.backend:
        cfg = with_overrides(cfg, attention__backend=args.backend)
    if args.cache_layout:
        cfg = with_overrides(cfg, attention__cache_layout=args.cache_layout)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sampler = None
    if (args.temperature is not None or args.top_k is not None
            or args.top_p is not None):
        sampler = make_sampler(
            temperature=args.temperature if args.temperature is not None else 1.0,
            top_k=args.top_k,
            top_p=args.top_p,
        )
    tracer = (Tracer() if args.trace_out or args.trace_events else None)
    draft = (DraftConfig(k=args.draft_k, time_steps=args.draft_time_steps)
             if args.draft_k else None)
    engine_kwargs = dict(num_slots=args.slots,
                         max_seq=args.max_seq, sampler=sampler,
                         page_size=args.page_size, num_pages=args.num_pages,
                         share_prefix=args.share_prefix,
                         prefix_cache_pages=args.prefix_cache_pages,
                         prefill_chunk=args.prefill_chunk, draft=draft,
                         tracer=tracer,
                         mesh_shards=(args.mesh_shards
                                      if args.mesh_shards > 1 else None))
    if args.replicas > 1:
        engine = ReplicatedEngine(model, params, replicas=args.replicas,
                                  **engine_kwargs)
        engines = engine.engines
    else:
        engine = ServingEngine(model, params, **engine_kwargs)
        engines = [engine]

    rng = np.random.default_rng(0)
    system = (rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
              if args.share_prefix else np.empty(0, np.int32))
    reqs = []
    for uid in range(args.requests):
        plen = int(rng.integers(4, 24))
        prompt = np.concatenate(
            [system, rng.integers(0, cfg.vocab_size, plen).astype(np.int32)]
        )
        reqs.append(Request(uid=uid, prompt=prompt,
                            max_new_tokens=int(rng.integers(8, 24))))

    # with a persistent prefix cache, submit in two drain-separated waves:
    # wave 2's admissions revive the pages wave 1 parked on its way out
    waves = ([reqs[: len(reqs) // 2], reqs[len(reqs) // 2:]]
             if args.prefix_cache_pages else [reqs])

    t0 = time.time()
    ticks = 0
    for wave in waves:
        for req in wave:
            engine.submit(req)
        while engine.has_pending_work:
            engine.step()
            ticks += 1
            if ticks % 8 == 0:
                done = sum(r.done for r in reqs)
                extra = ""
                if engines[0].paged:
                    ss = [e.stats() for e in engines]
                    used = sum(s["pages_used"] for s in ss)
                    total = sum(s["pages_used"] + s["pages_free"] for s in ss)
                    pre = sum(s["preempted_now"] for s in ss)
                    extra = f" pages={used}/{total} preempted={pre}"
                active = sum(len(e.active) for e in engines)
                queued = sum(len(e.queue) for e in engines)
                if args.replicas > 1:
                    queued += len(engine.queue)
                print(f"tick {ticks:4d}: active={active} "
                      f"queued={queued} done={done}{extra}")
            if ticks > 500:
                break
        if ticks > 500:
            break
    dt = time.time() - t0

    total_tokens = sum(len(r.out_tokens) for r in reqs)
    print(f"\n{sum(r.done for r in reqs)}/{len(reqs)} requests finished, "
          f"{total_tokens} tokens in {ticks} engine ticks ({dt:.1f}s, "
          f"{total_tokens / max(dt, 1e-9):.1f} tok/s on "
          f"{jax.devices()[0].platform}, compiles included)")
    print(f"kv cache: {engine.kv_cache_nbytes() / 2**20:.2f} MiB "
          f"(impl={cfg.attention.impl}, storage={cfg.attention.spike_storage}, "
          f"backend={cfg.attention.backend})")
    if args.mesh_shards > 1:
        shard_bytes = engines[0].kv_shard_nbytes()
        per = " + ".join(f"{b / 2**20:.2f}" for b in shard_bytes)
        print(f"tensor parallel: {args.mesh_shards} shards over "
              f"{len(jax.devices())} devices, per-shard kv pool "
              f"{per} MiB" + (" (each replica)" if args.replicas > 1 else ""))
    if args.replicas > 1:
        counts = engine.request_counts()
        print(f"replicas: {args.replicas} engines, dispatched="
              f"{'/'.join(map(str, counts))} requests, joint peak "
              f"concurrency {engine.max_concurrency_seen} rows")
    print(f"prefill compiles: {sum(e.num_prefill_compiles for e in engines)} "
          f"(power-of-two length buckets)")
    for i, e in enumerate(engines):
        tag = f"replica {i} " if args.replicas > 1 else ""
        if e.paged:
            s = e.stats()
            print(f"{tag}paged scheduler: page_size={s['page_size']} "
                  f"pool={s['num_pages']} pages (peak used {s['peak_pages_used']}), "
                  f"preemptions={s['preemptions']} resumes={s['resumes']} "
                  f"replay_steps={s['replay_steps']} migrations={s['migrations']} "
                  f"max_concurrency={s['max_concurrency_seen']} "
                  f"queue_wait={s['queue_wait_ticks']} ticks")
            if s["prefill_chunk"]:
                print(f"{tag}chunked prefill: chunk={s['prefill_chunk']} tokens, "
                      f"{s['chunked_prefills']} admissions in "
                      f"{s['prefill_chunks_run']} chunks "
                      f"(skipped={s['prefill_chunks_skipped']} shared-resident, "
                      f"pauses={s['prefill_pauses']} aborts={s['prefill_aborts']})")
            if s.get("prefix_cache_pages"):
                looked_up = s["cache_hits"] + s["cache_misses"]
                rate = s["cache_hits"] / max(looked_up, 1)
                print(f"{tag}prefix cache: capacity={s['prefix_cache_pages']} "
                      f"pages, {s['cache_inserts']} inserts, "
                      f"{s['cache_hits']} hits "
                      f"({rate:.0%} of {looked_up} lookups), "
                      f"evictions={s['cache_evictions']} "
                      f"resident_now={s['cached_pages_now']}")
        if draft is not None:
            s = e.stats()
            drafted = s["spec_drafted_tokens"]
            rate = s["spec_accepted_tokens"] / max(drafted, 1)
            print(f"{tag}speculative decode: k={draft.k}, {s['spec_ticks']} "
                  f"spec ticks, {drafted} drafted / {s['spec_accepted_tokens']} "
                  f"accepted ({rate:.1%}), verify dispatches="
                  f"{s['verify_dispatches']} draft={s['draft_dispatches']}")
            if s["share_prefix"]:
                print(f"{tag}prefix sharing: "
                      f"shared_page_hits={s['shared_page_hits']} "
                      f"cow_copies={s['cow_copies']} "
                      f"shared_pages_now={s['shared_pages_now']}")
    if tracer is not None:
        m = engine.metrics
        ttft, itl = m.histogram("ttft_ticks"), m.histogram("intertoken_wall_s")
        print(f"latency: ttft p50={ttft.percentile(50):.0f} "
              f"p95={ttft.percentile(95):.0f} ticks over {ttft.count} requests; "
              f"inter-token p50={itl.percentile(50) * 1e3:.1f}ms "
              f"p95={itl.percentile(95) * 1e3:.1f}ms over {itl.count} tokens")
        print(f"trace: {tracer.events_emitted} events emitted "
              f"({tracer.events_dropped} dropped)")
        if args.trace_events:
            for sig in tracer.signatures()[-args.trace_events:]:
                print(f"  event {sig}")
        if args.trace_out:
            export_perfetto(tracer.events(), args.trace_out)
            print(f"trace written to {args.trace_out} "
                  f"(open at ui.perfetto.dev)")
    for r in reqs[:3]:
        print(f"  req {r.uid}: prompt[{len(r.prompt)}] -> {r.out_tokens[:10]}...")


if __name__ == "__main__":
    main()
