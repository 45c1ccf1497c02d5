"""Serving engine: a scheduler over decode rows and (optionally) a shared
KV page pool.

vLLM-style at the granularity JAX likes (static shapes):
  * ``B`` decode rows; requests queue up and are admitted FCFS into free
    rows by running prefill for one request at a time;
  * prefill prompt lengths are **bucketed to the next power of two**
    (padded + masked), so the jitted prefill compiles O(log max_seq) times
    instead of once per distinct prompt length (`num_prefill_compiles`
    exposes the count);
  * one fused decode step advances ALL active rows each tick (inactive
    rows decode garbage that is masked out — the static-shape trade);
  * finished sequences (EOS or max_len) free their row immediately.

Sampling seeds (RNG contract v2): every request carries a uint32
``Request.seed`` (defaulting to the engine-wide default, which equals what
a manual batch-1 prefill+decode loop derives), and the engine hands the
model a per-row seed vector each call.  Because the SSA counter RNG is
keyed by (seed, layer, t_step, absolute position, channel) — never by
batch row, pad bucket or cache extent — a request's token stream is
invariant to which row it occupies and how wide the synced block tables
are.  That buys the scheduler three freedoms this module implements:

  * **row migration** — a preempted request resumes into *any* free row;
  * **extent-bounded spiking decode** — every impl (ann AND ssa/spikformer)
    decodes through pow2-bucketed block tables, so no decode tick
    materialises a ``max_seq``-extent tensor;
  * **copy-on-write prefix sharing** (``share_prefix=True``) — requests
    with the same seed and a common prompt prefix map the same physical
    pages; a page is copied the first time an owner writes into it
    (sliding-window wrap / divergence), so shared pages stay pristine.

Chunked paged prefill (``prefill_chunk=``, paged layout): instead of
staging a whole prompt in a slab-row cache and scattering it into pages,
the engine splits each prompt into page-aligned chunks and prefills
chunk-by-chunk **directly into pool pages** through block-table indirection
(the backends' prefix-extend path: a chunk attends over the previously
written pages plus itself).  Because RNG contract v2 keys every SSA draw by
(request seed, layer, head, t_step, absolute position), a chunked prefill
samples exactly the spikes the one-shot prefill samples — streams stay
bit-identical — while peak prefill memory drops from O(prompt bucket) to
O(chunk) and pages are claimed per chunk: admission no longer waits for a
full-prompt page grant, and a request mid-prefill pauses/resumes at chunk
boundaries (or is rolled back entirely when running requests need its
pages).  Prompts longer than the smallest sliding-window extent (or than
``max_seq``) keep the one-shot slab-staged fallback, exactly as they
already bypass pow2 bucketing.  With ``share_prefix=True``, chunks fully
covered by already-resident shared prefix pages are skipped outright.

Cache layouts (``AttentionConfig.cache_layout``):

``slab`` — each row owns a contiguous fixed-size cache region (the cache is
one batched tree — row i is batch row i).  Simple, but memory is reserved
for ``num_slots * max_seq`` rows whatever the traffic looks like.

``paged`` — cache leaves are a shared :class:`~repro.serving.paging.PagePool`
(``(num_pages, page_size, ...)``) and the engine becomes a scheduler over
it: admission requires free pages for the prompt, each tick grows active
requests by a page when they cross a page boundary, and on pool exhaustion
the engine preempts a victim (LRU-of-idle: least-recently-scheduled first —
with lock-step decode all active rows tie, so this degenerates to the most
recently admitted request).  Preempted requests release their pages *and*
their row; they resume into any free row by re-running the (bit-identical)
bucketed prompt prefill and then *replaying* their generated tokens through
the decode step.  Token streams are bit-identical to the slab engine for
the same seeds and arrival order — for any sampler while pages are ample;
once page pressure defers admissions or preempts, the per-tick sampler-key
sequence shifts, so the cross-schedule guarantee is for per-tick-key-free
(greedy) sampling — and ``kv_cache_nbytes`` reflects the pool actually
allocated instead of ``num_slots * max_seq`` worth of slabs.  ``stats()``
reports occupancy / queue-wait / preemption / migration / sharing counters.

Preempted requests resume through the same chunked machinery: the prompt
re-prefills chunk-by-chunk into per-chunk-claimed pages (pausable at chunk
boundaries when the pool is dry, rolled back entirely when a running
request needs the pages), and the replay growth region is granted page by
page from the free list — a resume never preempts a running request.

Self-speculative decoding (``draft=DraftConfig(...)``): each tick a cheap
draft — the same weights at a reduced SSA time-step count, an ``ann``
draft, or an explicit (model, params) pair — proposes up to ``k`` tokens
per row one at a time, then ONE verify prefix-extend of the target scores
the whole proposal window (``decode_step`` with ``logits_at=None`` returns
logits at every chunk position) and the longest agreeing prefix commits.
Exact under greedy: RNG contract v2 keys every draw by absolute position,
so the verify chunk's per-position logits are bit-identical to one-at-a-
time decode and accept/reject is a pure token comparison.  Rejected
suffixes rewind by host-side position bookkeeping only (stale cache
entries are causally masked and re-written before ever being attended);
the draft's KV lives in its own small page pool, dropped wholesale on
preemption/finish and rebuilt by a catch-up prefix-extend, so speculation
composes with preemption, migration, and prefix sharing.  Speculative
page needs (target span and draft alike) come from the free list only —
a dry pool truncates the proposal window instead of evicting anyone.

Sampling is pluggable (``sampler=``, see `repro.serving.sampling`): greedy
argmax by default, temperature / top-k / top-p via ``make_sampler``.

Observability (see docs/observability.md): a
:class:`~repro.obs.metrics.MetricsRegistry` is always attached (host-side
integer bookkeeping only — zero device transfers) and backs every counter
the engine exposes; ``stats()`` is a frozen snapshot of it and
``snapshot()`` adds histogram summaries (TTFT, inter-token latency,
queue wait, tick-phase timings).  Passing ``tracer=`` a
:class:`~repro.obs.trace.Tracer` additionally records one typed event per
scheduler decision (admit / preempt / migrate / CoW / page grant / ...)
and splits the tick into named timed phases (``schedule`` /
``host_stage`` / ``dispatch`` / ``device_sync`` / ``sample``, plus
``draft`` / ``verify`` on speculative engines) —
exportable to Perfetto via :func:`repro.obs.perfetto.export_perfetto`.
Tracing never touches device state, so a traced engine's token streams
are bit-identical to an untraced one's.
"""
from __future__ import annotations

import collections
import inspect
import time
from dataclasses import dataclass, field
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry, annotate
from repro.obs.trace import Tracer

from .paging import pages_for_rows
from .sampling import Sampler, greedy


def _dev(arr: np.ndarray) -> jax.Array:
    """Host -> device at the dispatch boundary, always through a copy.

    ``jnp.asarray`` of a host int32 array is zero-copy on CPU and dispatch
    is async, so handing JAX a buffer the scheduler later mutates (or
    reuses) is a latent nondeterminism race (the PR-3 ``slot_pos`` bug).
    Every host-owned array — tokens, positions, write offsets, seeds, block
    tables, write/scrub tables — crosses into jit through this helper.
    """
    return jnp.asarray(np.array(arr, copy=True))


@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int = 32
    # stop on any of these token ids; modern tokenizers ship several stop
    # ids, so an int, a set/frozenset, or any iterable of ints is accepted
    eos_id: Union[int, frozenset, set, tuple, list, None] = None
    # uint32 sampling seed (RNG contract v2); None = the engine default,
    # which matches a manual batch-1 loop with rng=None.  Requests only
    # share prefix pages with requests holding the same seed.
    seed: Optional[int] = None
    out_tokens: list = field(default_factory=list)
    done: bool = False

    def eos_ids(self) -> frozenset:
        if self.eos_id is None:
            return frozenset()
        if isinstance(self.eos_id, (int, np.integer)):
            return frozenset((int(self.eos_id),))
        return frozenset(int(t) for t in self.eos_id)


@dataclass(frozen=True)
class DraftConfig:
    """Self-speculative decode configuration (``ServingEngine(draft=...)``).

    Each engine tick, a cheap **draft** model proposes up to ``k`` tokens
    per active row; the target model scores the whole proposal in ONE
    verify prefix-extend (``decode_step(logits_at=None)`` returns logits at
    every chunk position) and commits the longest accepted prefix plus one
    correction/bonus token.  Acceptance compares the draft's token against
    the token the target's sampler picks from the *verifier's* logits at
    that position — exact under greedy (the committed stream is
    token-identical to non-speculative decode), and distribution-exact
    under temperature sampling (every committed token is a sampler draw
    from target logits; only the per-tick key schedule differs).

    The draft is derived from the target unless ``model`` is given:

    * ``time_steps`` — same SSA weights run with fewer stochastic time
      steps (``attention.ssa_time_steps``), the reduced-step self-draft.
      Defaults to ``max(1, T // 2)`` for ssa/spikformer targets.
    * ``impl`` — a different registry backend over the same weights (e.g.
      ``"ann"`` for a non-spiking draft; forced onto the xla backend).

    Draft KV state lives beside the target's: a private slab cache, or —
    paged layout — a private ``num_pages``-page pool (default: ample,
    every row can draft to ``max_seq``) whose grants/releases are traced
    with ``pool="draft"`` and counted by ``draft_pages_*``.  Speculation
    never preempts anyone: when target *or* draft pages run dry the row
    simply drafts fewer (or zero) tokens that tick.
    """

    k: int = 4
    time_steps: Optional[int] = None
    impl: Optional[str] = None
    num_pages: Optional[int] = None
    model: Optional[object] = None
    params: Optional[object] = None
    # adaptive throttling: track a per-row EMA of the accept rate and
    # shrink that row's k ceiling while the EMA sits below accept_floor
    # (down to k=0, a plain decode tick), probing one k wider every
    # probe_period spec ticks.  Hard rows stop paying for doomed draft
    # dispatches; easy rows keep the full k.  Throttle steps are counted
    # in stats()["spec_throttled"].  Committed streams are unchanged
    # (acceptance is per-token; a smaller k only shortens proposals).
    adaptive: bool = False
    accept_floor: float = 0.35
    ema_alpha: float = 0.5
    probe_period: int = 4


def _default_page_size(max_seq: int) -> int:
    """Largest power of two <= 16 dividing max_seq (page_size | max_seq is
    required so the full block-table span equals the slab extent exactly)."""
    ps = 1
    while ps < 16 and max_seq % (ps * 2) == 0:
        ps *= 2
    return ps


def _scrub_pages(cache: list, pages: jax.Array) -> list:
    """Reset the given page ids to the pristine zero-page fill.

    Recycled pages go back to the free list through here: the slab engine
    re-initialises a whole slot region at admission, so for bit-identical
    behaviour a recycled page must look exactly like a never-used one when
    it is gathered beyond a request's written rows (enc(0) spikes / zeros /
    pos = -1, not the previous tenant's tail).  ``pages`` is fixed-width
    (pages_per_seq), padded with ``PAGE_SCRATCH`` — scrubbing scratch is
    harmless and keeps the compile count at one.  Pages still referenced by
    another owner (prefix sharing) never reach this function.
    """
    from repro.attention import PAGE_ZERO

    def per_slot(pool_d: dict) -> dict:
        out = dict(pool_d)
        for name, pool in pool_d.items():
            if name == "bt":
                continue
            zero = pool[:, PAGE_ZERO][:, None]      # (steps, 1, ps, ...)
            out[name] = pool.at[:, pages].set(
                jnp.broadcast_to(zero, (pool.shape[0], pages.shape[0])
                                 + pool.shape[2:])
            )
        return out

    return [per_slot(c) for c in cache]


def _scatter_pages(cache: list, row_cache: list, wt: jax.Array) -> list:
    """Write a batch-1 slab row cache into the page pool.

    ``wt``: (pages_per_seq,) int32 write table — column j receives slab rows
    [j*ps:(j+1)*ps); unallocated columns sink to the scratch page (their
    slab rows hold the init fill, so the zero page never needs writing).
    Window slots have shorter slab extents and consume a prefix of ``wt``;
    rows padding the last partial page are never gathered back.  Columns
    holding *shared* prefix pages are written too: the sharer's prefill of
    the common prefix produces bit-identical rows (same seed, same
    positions — RNG contract v2), so the write is a byte-level no-op.
    """
    def per_slot(pool_d: dict, row_d: dict) -> dict:
        out = dict(pool_d)
        ps = pool_d["pos"].shape[-1]
        for name, pool in pool_d.items():
            if name == "bt":
                continue
            r = row_d[name][:, 0]                      # (steps, S, ...)
            s = r.shape[1]
            cols = -(-s // ps)
            pad = cols * ps - s
            if pad:
                r = jnp.pad(r, ((0, 0), (0, pad)) + ((0, 0),) * (r.ndim - 2))
            tiles = r.reshape((r.shape[0], cols, ps) + r.shape[2:])
            out[name] = pool.at[:, wt[:cols]].set(tiles.astype(pool.dtype))
        return out

    return [per_slot(c, rc) for c, rc in zip(cache, row_cache)]


def _copy_page(cache: list, src, dst) -> list:
    """Copy one page's content (every leaf, every slot) src -> dst: the
    copy-on-write divergence step.  The copy is byte-identical, so gathers
    through either id read the same rows until the owner's next write."""
    out = []
    for slot_d in cache:
        nd = dict(slot_d)
        for name, pool in slot_d.items():
            if name == "bt":
                continue
            nd[name] = pool.at[:, dst].set(pool[:, src])
        out.append(nd)
    return out


# Pool-surgery helpers are pure functions of (cache, operands): jit them
# once at module scope so every engine instance shares the compile cache.
_scrub_jit = jax.jit(_scrub_pages)
_scatter_jit = jax.jit(_scatter_pages)
_copy_jit = jax.jit(_copy_page)


def _model_jit(model, key: str, make):
    """Memoise jitted model entry points on the model instance itself, so
    engines over the same model (tests build many) share compiled code
    instead of re-tracing per engine.

    The jitted function is named after its key (``serve_decode_seeded``,
    ``serve_chunk_tp4`` for ``chunk@tp4``), so device profiles show the
    program as ``jit_serve_<key>`` rather than ``jit__lambda``."""
    cache = model.__dict__.setdefault("_serving_jit_cache", {})
    if key not in cache:
        fn = make()
        fn.__name__ = fn.__qualname__ = "serve_" + key.replace("@", "_")
        cache[key] = jax.jit(fn)
    return cache[key]


# ---------------------------------------------------------------------------
# tensor-parallel (mesh_shards=) wrappers
#
# The serving TP rules live in a contextvar that model code reads at TRACE
# time, so the rules must be installed inside the traced body, not around
# the jit call.  Outputs are pinned to their canonical shardings (cache
# leaves head-sharded, logits replicated) so the cache round-trips every
# tick with a stable layout — without this GSPMD may pick a different
# output sharding per entry point and reshard (+ recompile) on every hop
# between decode / scrub / scatter / copy.
# ---------------------------------------------------------------------------


def _tp_wrap_model(make, rules, kv_heads: int):
    """Wrap a (logits, cache)-returning model entry point for serving TP."""
    from repro.distributed.sharding import (
        constrain_serving_cache, reset_rules, use_rules,
    )

    def make_wrapped():
        fn = make()

        def wrapped(*args):
            tok = use_rules(rules)
            try:
                logits, cache = fn(*args)
            finally:
                reset_rules(tok)
            logits = jax.lax.with_sharding_constraint(
                logits,
                jax.sharding.NamedSharding(
                    rules.mesh, jax.sharding.PartitionSpec()
                ),
            )
            return logits, constrain_serving_cache(cache, rules, kv_heads)

        return wrapped

    return make_wrapped


def _tp_wrap_cache(make, rules, kv_heads: int):
    """Wrap a cache-returning pool-surgery function for serving TP."""
    from repro.distributed.sharding import constrain_serving_cache

    def make_wrapped():
        fn = make()

        def wrapped(*args):
            return constrain_serving_cache(fn(*args), rules, kv_heads)

        return wrapped

    return make_wrapped


class _NullCtx:
    """Reusable no-op context: the untraced engine's phase 'timer'."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class _PhaseTimer:
    """Times one named tick phase; emits a histogram sample + phase event
    and opens the profiler span ``repro/<name>`` around the phase, so the
    device trace sees the tick's structure on its own clock."""

    __slots__ = ("eng", "name", "span", "t0")

    def __init__(self, eng, name):
        self.eng = eng
        self.name = name
        self.span = annotate(f"repro/{name}")

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self.span.__exit__(*exc)
        self.eng.metrics.observe(f"phase_{self.name}_s", dur)
        self.eng._trace("phase", phase=self.name, dur_s=dur)
        return False


def _counter_property(name: str, doc: str) -> property:
    """Read-only view of a registry counter under a legacy attribute name
    (tests and benchmarks read ``engine.preemptions`` etc. directly)."""
    return property(lambda self: self.metrics.counter(name).value, doc=doc)


def _gauge_max_property(name: str, doc: str) -> property:
    return property(lambda self: self.metrics.gauge(name).max, doc=doc)


@dataclass
class _ChunkedPrefill:
    """An admission mid-chunked-prefill: the head-of-line request, the row
    reserved for it, and the pages claimed so far.  ``done`` is the chunk
    boundary reached; pages beyond it hold nothing yet."""

    req: Request
    slot: int
    pages: list                    # shared prefix pages + fresh, in order
    keys: list                     # full-prompt-page keys (registration)
    shared_rows: int               # rows covered by claimed shared pages
    done: int = 0                  # tokens prefilled so far
    logits: Optional[jax.Array] = None
    # resume re-prefill (not a fresh admission): on completion the row is
    # re-seated and its recorded tokens replayed instead of sampling a
    # first token; on rollback the request returns to the preempted list
    resume: bool = False


class ServingEngine:
    def __init__(self, model, params, *, num_slots: int, max_seq: int,
                 rng_seed: int = 0, sampler: Optional[Sampler] = None,
                 num_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 share_prefix: bool = False,
                 prefix_cache_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 draft: Optional[DraftConfig] = None,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 mesh_shards: Optional[int] = None,
                 replica_id: Optional[int] = None):
        self.model = model
        self.params = params
        self.b = num_slots
        self.max_seq = max_seq
        self.sampler = sampler if sampler is not None else greedy
        self.queue: collections.deque[Request] = collections.deque()
        self.active: dict[int, Request] = {}          # row -> request
        self.slot_pos = np.zeros(num_slots, np.int32)  # next position per row
        self.slot_seeds = np.zeros(num_slots, np.uint32)
        self.key = jax.random.PRNGKey(rng_seed)
        # observability: registry always on (host-side only); tracer opt-in
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        for name in ("ticks", "queue_wait_ticks", "requests_submitted",
                     "requests_finished", "tokens_sampled", "compile_events"):
            m.counter(name)
        for name in ("concurrency", "occupancy"):
            m.gauge(name)
        for name in ("ttft_ticks", "ttft_wall_s", "intertoken_ticks",
                     "intertoken_wall_s", "queue_wait_ticks",
                     "phase_schedule_s", "phase_host_stage_s",
                     "phase_dispatch_s", "phase_device_sync_s",
                     "phase_sample_s"):
            m.histogram(name)
        self._ticks = m.counter("ticks")

        from repro.attention import derive_request_seeds

        # the seed a request gets when it doesn't bring one: identical to
        # what a manual batch-1 loop derives from rng=None, so any engine
        # row reproduces that loop token-for-token (row invariance)
        self.default_seed = int(
            np.asarray(jax.device_get(derive_request_seeds(None, 1)))[0]
        )

        # ---- tensor parallelism over the `model` mesh axis ----
        # Params replicate, attention heads + KV-cache leaves shard (see
        # ServingTPRules: every collective is data movement, never a float
        # reduction, so sharded streams are bit-identical to unsharded).
        # `replica_id` only tags emitted events; the data-parallel layer
        # itself lives in serving/replicas.py.
        self.mesh_shards = int(mesh_shards) if mesh_shards else 1
        if self.mesh_shards < 1:
            raise ValueError(f"mesh_shards must be >= 1, got {mesh_shards}")
        self.replica_id = replica_id
        self._event_tags: dict = {}
        if replica_id is not None:
            self._event_tags["replica"] = int(replica_id)
        self.mesh = None
        self._tp_rules = None
        attn_cfg = getattr(getattr(model, "cfg", None), "attention", None)
        self._kv_heads = getattr(attn_cfg, "num_kv_heads", 1) or 1
        if self.mesh_shards > 1:
            from repro.distributed.sharding import ServingTPRules
            from repro.launch.mesh import make_local_mesh

            ndev = len(jax.devices())
            if ndev < self.mesh_shards:
                raise ValueError(
                    f"mesh_shards={self.mesh_shards} needs at least that "
                    f"many devices, found {ndev} (CPU hosts: set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)"
                )
            self.mesh = make_local_mesh(model=self.mesh_shards)
            self._tp_rules = ServingTPRules(self.mesh)
            self._event_tags["shards"] = self.mesh_shards
            self.params = jax.device_put(
                params,
                jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()
                ),
            )

        # models outside the decoder-LM family predate the seeds kwarg;
        # they keep their rng-derived streams (no serving identity contract)
        decode_params = inspect.signature(model.decode_step).parameters
        self._seeded = "seeds" in decode_params
        self._has_logits_at = "logits_at" in decode_params
        if self._seeded:
            self._decode = self._jit_model(
                model, "decode_seeded",
                lambda: lambda p, batch, cache, idx, seeds: model.decode_step(
                    p, batch, cache, idx, seeds=seeds
                ),
            )
        else:
            self._decode = self._jit_model(
                model, "decode",
                lambda: lambda p, batch, cache, idx: model.decode_step(
                    p, batch, cache, idx
                ),
            )

        a = getattr(getattr(model, "cfg", None), "attention", None)
        self.layout = getattr(a, "cache_layout", "slab") if a is not None else "slab"
        self.paged = self.layout == "paged"
        self.share_prefix = bool(share_prefix)
        if self.share_prefix and not self.paged:
            raise ValueError(
                "share_prefix=True requires the paged cache layout "
                "(AttentionConfig.cache_layout='paged'); this model is "
                f"configured for layout={self.layout!r}"
            )
        self.prefix_cache_pages = int(prefix_cache_pages or 0)
        if self.prefix_cache_pages < 0:
            raise ValueError(
                f"prefix_cache_pages must be >= 0, got {prefix_cache_pages}"
            )
        if self.prefix_cache_pages and not (self.paged and self.share_prefix):
            raise ValueError(
                "prefix_cache_pages requires share_prefix=True on the paged "
                "cache layout (the cache parks shared-prefix registrations); "
                f"got layout={self.layout!r}, share_prefix={share_prefix}"
            )
        self._cache_on = self.prefix_cache_pages > 0
        if self.paged:
            from repro.attention import NUM_RESERVED_PAGES

            from .paging import BlockTables, PagePool

            ps = page_size if page_size is not None else _default_page_size(max_seq)
            if max_seq % ps:
                raise ValueError(
                    f"page_size={ps} must divide max_seq={max_seq} so the "
                    "block-table span matches the slab cache extent"
                )
            self.pages_per_seq = max_seq // ps
            if num_pages is None:
                # ample default: every row can grow to max_seq — identical
                # behaviour to the slab engine; callers shrink it to trade
                # memory for preemptions
                num_pages = NUM_RESERVED_PAGES + num_slots * self.pages_per_seq
            for name in ("preemptions", "resumes", "replay_steps",
                         "migrations", "shared_page_hits", "cow_copies",
                         "chunked_prefills", "prefill_chunks_run",
                         "prefill_chunks_skipped", "prefill_pauses",
                         "prefill_aborts", "pages_granted", "pages_shared",
                         "pages_released", "pages_retired",
                         "cache_inserts", "cache_hits", "cache_misses",
                         "cache_evictions"):
                m.counter(name)
            m.gauge("pages_used")
            m.gauge("cache_pages")
            self.pool = PagePool(num_pages, ps,
                                 cache_pages=self.prefix_cache_pages,
                                 on_event=self._pool_event)
            if self.pool.num_usable < self.pages_per_seq:
                raise ValueError(
                    f"pool of {num_pages} pages cannot back even one "
                    f"request ({self.pages_per_seq} pages of {ps} rows "
                    f"needed for max_seq={max_seq})"
                )
            self.tables = BlockTables(num_slots, self.pages_per_seq)
            if self._tp_rules is None:
                self._scrub = _scrub_jit
                self._scatter = _scatter_jit
                self._copy = _copy_jit
            else:
                # pool surgery must preserve the head-sharded leaf layout;
                # memoised per (model, shard count) like the model entries
                self._scrub = _model_jit(
                    model, self._jit_key("scrub"),
                    _tp_wrap_cache(
                        lambda: _scrub_pages, self._tp_rules, self._kv_heads),
                )
                self._scatter = _model_jit(
                    model, self._jit_key("scatter"),
                    _tp_wrap_cache(
                        lambda: _scatter_pages, self._tp_rules,
                        self._kv_heads),
                )
                self._copy = _model_jit(
                    model, self._jit_key("copy"),
                    _tp_wrap_cache(
                        lambda: _copy_page, self._tp_rules, self._kv_heads),
                )
            self.cache = self._place_cache(model.init_cache(
                num_slots, max_seq, layout="paged",
                num_pages=num_pages, page_size=ps,
            ))
            # per-layer rolling extents (sliding windows) — the engine needs
            # them to know which columns a decode tick writes (CoW guard)
            extents = {max_seq}
            slot_window = getattr(model, "_slot_window", None)
            if callable(slot_window) and hasattr(model, "pattern"):
                for s_idx in range(len(model.pattern)):
                    w = model._slot_window(s_idx)
                    extents.add(min(w, max_seq) if w is not None else max_seq)
            self._slot_extents = sorted(extents)
            self._preempted: list[Request] = []
            self._admit_order: dict[int, int] = {}    # uid -> admission seq
            self._last_row: dict[int, int] = {}       # uid -> preempted row
            self._admit_seq = 0
            self._table_widths: set[int] = set()      # decode compile sigs
            # prefix sharing state: sha256(seed, prefix tokens) -> page id,
            # plus the reverse map for retiring entries when pages die
            self._prefix_map: dict[bytes, int] = {}
            self._page_key: dict[int, bytes] = {}
            # ---- chunked prefill (prefix-extend straight into pages) ----
            # default = one page per chunk; prefill_chunk=0 restores the
            # one-shot slab-staged prefill.  Needs the model to thread
            # per-request seeds AND expose decode_step(logits_at=) (the
            # chunk call is a multi-token decode whose last real token's
            # logits seed sampling).
            can_chunk = self._seeded and "logits_at" in decode_params
            if prefill_chunk is None:
                self.prefill_chunk = ps if can_chunk else 0
            else:
                pc = int(prefill_chunk)
                if pc < 0:
                    raise ValueError(f"prefill_chunk must be >= 0, got {pc}")
                if pc and not can_chunk:
                    raise ValueError(
                        "prefill_chunk requires a model whose decode_step "
                        "accepts seeds= and logits_at= (the chunked "
                        "prefix-extend call); this model does not"
                    )
                if pc and pc % ps:
                    raise ValueError(
                        f"prefill_chunk={pc} must be page-aligned "
                        f"(a multiple of page_size={ps})"
                    )
                self.prefill_chunk = pc
            self._chunk = None
            if self.prefill_chunk:
                self._chunk = self._jit_model(
                    model, "chunk",
                    lambda: lambda p, batch, cache, idx, seeds, last:
                        model.decode_step(
                            p, batch, cache, idx, seeds=seeds, logits_at=last
                        ),
                )
            self._inflight: Optional[_ChunkedPrefill] = None
            self._chunk_signatures: set[tuple[int, int]] = set()
        else:
            if num_pages is not None or page_size is not None:
                raise ValueError(
                    "num_pages/page_size require the paged cache layout "
                    "(AttentionConfig.cache_layout='paged'); this model is "
                    f"configured for layout={self.layout!r}"
                )
            if prefill_chunk is not None:
                raise ValueError(
                    "prefill_chunk requires the paged cache layout "
                    "(AttentionConfig.cache_layout='paged'); this model is "
                    f"configured for layout={self.layout!r}"
                )
            self.cache = self._place_cache(model.init_cache(num_slots, max_seq))
        self._submit_tick: dict[int, int] = {}
        self._submit_wall: dict[int, float] = {}
        self._last_token: dict[int, tuple[int, float]] = {}  # (tick, wall)
        # requests finished at admission (prefill-only, max_new_tokens=1):
        # collected here so the tick that admitted them returns them
        self._admit_finished: list[Request] = []

        # Bucketed prefill needs the model to expose `logits_at` (read the
        # real last token's logits out of a padded prompt); models without
        # it fall back to one exact-length prefill per request.
        prefill_params = inspect.signature(model.prefill).parameters
        self._bucketed = "logits_at" in prefill_params
        self._prefill_seeded = "seeds" in prefill_params
        if self._bucketed:
            if self._prefill_seeded:
                self._prefill = self._jit_model(
                    model, "prefill_seeded",
                    lambda: lambda p, batch, cache, last, seeds: model.prefill(
                        p, batch, cache, logits_at=last, seeds=seeds
                    ),
                )
            else:
                self._prefill = self._jit_model(
                    model, "prefill",
                    lambda: lambda p, batch, cache, last: model.prefill(
                        p, batch, cache, logits_at=last
                    ),
                )
        else:
            self._prefill = None
        # pristine single-row cache: the fill state padded prompt rows are
        # reset to after prefill (zeros / packed enc(0) / pos=-1); also the
        # template every admission prefills from (functional updates never
        # mutate it)
        self._init_row = self._place_cache(model.init_cache(1, max_seq))
        # smallest per-layer cache extent along the sequence axis (leaves are
        # (L, B, S, ...)): sliding-window layers allocate S = window, and a
        # padded prompt longer than that would evict real rows via the
        # prefill tail-keep — such prompts prefill at exact length instead
        extents = {
            leaf.shape[2]
            for leaf in jax.tree.leaves(self._init_row)
            if leaf.ndim >= 3
        }
        self._min_seq_extent = min(extents) if extents else max_seq
        self._prefill_buckets: set[int] = set()
        # ---- self-speculative decode (draft + verify prefix-extend) ----
        self.draft = draft
        self._draft_model = None
        if draft is not None:
            self._init_draft(draft)

    # ------------------------------------------------------------------
    # legacy counter attributes: read-only views over the metrics registry
    # (the registry is the single source of truth; these keep the public
    # surface tests and benchmarks read — `engine.preemptions` etc.)
    # ------------------------------------------------------------------
    steps_run = _counter_property("ticks", "Decode ticks run.")
    queue_wait_ticks = _counter_property(
        "queue_wait_ticks", "Total ticks requests spent queued.")
    preemptions = _counter_property("preemptions", "Requests preempted.")
    resumes = _counter_property("resumes", "Preempted requests resumed.")
    replay_steps = _counter_property("replay_steps", "Replayed decode ticks.")
    migrations = _counter_property("migrations", "Resumes into a new row.")
    shared_page_hits = _counter_property(
        "shared_page_hits", "Prefix pages mapped instead of re-prefilled.")
    cow_copies = _counter_property("cow_copies", "Copy-on-write page copies.")
    chunked_prefills = _counter_property(
        "chunked_prefills", "Admissions run through chunked prefill.")
    prefill_chunks_run = _counter_property(
        "prefill_chunks_run", "Prefix-extend chunk calls dispatched.")
    prefill_chunks_skipped = _counter_property(
        "prefill_chunks_skipped", "Chunks skipped (shared prefix resident).")
    prefill_pauses = _counter_property(
        "prefill_pauses", "Mid-prefill pauses (pool dry).")
    prefill_aborts = _counter_property(
        "prefill_aborts", "In-flight admissions rolled back.")
    max_concurrency_seen = _gauge_max_property(
        "concurrency", "Peak simultaneously active rows.")
    peak_pages_used = _gauge_max_property(
        "pages_used", "Peak pool pages in use.")

    # ------------------------------------------------------------------
    # tensor-parallel plumbing
    # ------------------------------------------------------------------
    def _jit_key(self, key: str) -> str:
        """Jit-cache key, suffixed per shard count: a sharded engine must
        never reuse an unsharded engine's traces (and vice versa) even when
        both wrap the same model instance."""
        return key if self._tp_rules is None else f"{key}@tp{self.mesh_shards}"

    def _jit_model(self, model, key: str, make):
        if self._tp_rules is not None:
            make = _tp_wrap_model(make, self._tp_rules, self._kv_heads)
        return _model_jit(model, self._jit_key(key), make)

    def _place_cache(self, cache):
        """Initial device placement for a cache tree: head-sharded payload
        leaves / replicated bookkeeping under TP, untouched otherwise."""
        if self._tp_rules is None:
            return cache
        from repro.distributed.sharding import serving_cache_shardings

        return jax.device_put(
            cache, serving_cache_shardings(cache, self.mesh, self._kv_heads)
        )

    # ------------------------------------------------------------------
    # observability plumbing
    # ------------------------------------------------------------------
    def _trace(self, kind: str, *, uid=None, row=None, **data):
        """Emit one lifecycle event if a tracer is attached (no-op and
        allocation-free otherwise — the zero-overhead-when-disabled path).
        Sharded / replicated engines tag every event (``shards=``,
        ``replica=``); plain engines add nothing, keeping their event
        signatures byte-identical to earlier releases."""
        tr = self.tracer
        if tr is not None:
            if self._event_tags:
                data = {**self._event_tags, **data}
            tr.emit(kind, tick=self._ticks.value, uid=uid, row=row, **data)

    def _phase(self, name: str):
        """Timed named tick phase when traced; a shared no-op otherwise."""
        return _NULL_CTX if self.tracer is None else _PhaseTimer(self, name)

    def _span(self, name: str):
        """Profiler span ``name`` when traced; the shared no-op otherwise."""
        return _NULL_CTX if self.tracer is None else annotate(name)

    def _compile_event(self, fn: str, signature):
        """A jit entry point is about to see a new signature."""
        self.metrics.inc("compile_events")
        self._trace("compile", fn=fn, signature=signature)

    def _pool_event(self, kind: str, **data):
        """PagePool hook: page-accounting counters + pass-through trace."""
        m = self.metrics
        if kind == "page_grant":
            m.inc("pages_granted", len(data["pages"]))
        elif kind == "page_share":
            m.inc("pages_shared")
        elif kind == "page_release":
            m.inc("pages_released", len(data["pages"]))
            m.inc("pages_retired", len(data["dead"]))
        elif kind == "cache_insert":
            m.inc("cache_inserts", len(data["pages"]))
            m.gauge("cache_pages").set(self.pool.num_cached)
        elif kind == "cache_hit":
            m.inc("cache_hits")
            m.gauge("cache_pages").set(self.pool.num_cached)
        elif kind == "cache_evict":
            m.inc("cache_evictions", len(data["pages"]))
            m.gauge("cache_pages").set(self.pool.num_cached)
        self._trace(kind, **data)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        if req.seed is None:
            req.seed = self.default_seed
        self._submit_tick[id(req)] = self.steps_run
        self._submit_wall[id(req)] = time.perf_counter()
        self.queue.append(req)
        self.metrics.inc("requests_submitted")
        self._trace("submit", uid=req.uid, prompt_len=len(req.prompt),
                    queued=len(self.queue))

    def _free_slots(self):
        busy = set(self.active)
        if self.paged and self._inflight is not None:
            busy.add(self._inflight.slot)
        return [i for i in range(self.b) if i not in busy]

    def _bucket(self, p: int) -> int:
        """Next power of two >= p, clamped to the slot's cache size.

        ``_admit`` additionally refuses buckets wider than the smallest
        per-layer cache extent (sliding-window layers), falling back to
        exact-length prefill for those prompts."""
        from repro.attention import next_pow2

        return min(next_pow2(p), self.max_seq)

    def _reset_pad_rows(self, row_cache, p: int):
        """Restore cache rows [p:] of a freshly prefilled single-row cache
        to their init-cache state.

        Padded prefill writes pad-token K/V into rows [p:bucket); resetting
        them to the pristine fill makes the cache bit-identical to an
        unpadded prefill of length ``p`` — the property that keeps bucketing
        invisible to every attention impl (pad positions are -1, so they
        never draw or mask in, but their K/V rows must also match the init
        fill for the cache trees to compare equal).
        Leaves carry the sequence axis at position 2 ((L, B, S, ...) stacked
        layout) with per-layer extents (sliding-window layers allocate
        S = window < max_seq); lower-rank leaves pass through untouched.
        """
        def clean(leaf, init_leaf):
            if leaf.ndim < 3:
                return leaf
            ext = leaf.shape[2]
            idx = jnp.arange(ext).reshape((1, 1, ext) + (1,) * (leaf.ndim - 3))
            return jnp.where(idx < p, leaf, init_leaf)

        return jax.tree.map(clean, row_cache, self._init_row)

    def _prefill_row(self, req: Request):
        """Run (bucketed) prefill for one request into a fresh slab row
        cache; returns (last-token logits, row cache)."""
        p = len(req.prompt)
        row_cache = self._init_row
        seeds = np.asarray([req.seed], np.uint32)
        if self._prefill is not None:
            pb = self._bucket(p)
            if pb < p or pb > self._min_seq_extent:
                # padding past a sliding-window layer's cache extent
                # would tail-keep the pad rows and evict real tokens;
                # such prompts (and any longer than max_seq) prefill at
                # exact length — correctness over compile reuse
                pb = p
            if pb not in self._prefill_buckets:
                self._prefill_buckets.add(pb)
                self._compile_event("prefill", pb)
            tokens = np.zeros((1, pb), np.int32)
            tokens[0, :p] = req.prompt
            # pad positions are -1: masked dead by the position-validity
            # checks on every impl, and their K/V rows are reset below
            positions = np.full((1, pb), -1, np.int32)
            positions[0, :p] = np.arange(p)
            args = (
                self.params,
                {"tokens": _dev(tokens), "positions": _dev(positions)},
                row_cache,
                jnp.asarray(p - 1, jnp.int32),
            )
            if self._prefill_seeded:
                args += (_dev(seeds),)
            with self._span("repro/prefill_dispatch"):
                logits, row_cache = self._prefill(*args)
            if pb != p:
                row_cache = self._reset_pad_rows(row_cache, p)
        else:
            tokens = _dev(np.asarray(req.prompt, np.int32)[None])
            positions = _dev(np.arange(p, dtype=np.int32)[None])
            kwargs = {"seeds": _dev(seeds)} if self._prefill_seeded else {}
            with self._span("repro/prefill_dispatch"):
                logits, row_cache = self.model.prefill(
                    self.params,
                    {"tokens": tokens, "positions": positions},
                    row_cache,
                    **kwargs,
                )
        return logits, row_cache

    def _start(self, slot: int, req: Request, logits):
        """Shared admission tail: sample the first token, activate the row."""
        m = self.metrics
        wait = self.steps_run - self._submit_tick.pop(id(req), self.steps_run)
        m.inc("queue_wait_ticks", wait)
        m.observe("queue_wait_ticks", wait)
        m.observe("ttft_ticks", wait)
        now = time.perf_counter()
        m.observe("ttft_wall_s", now - self._submit_wall.pop(id(req), now))
        self.key, sub = jax.random.split(self.key)
        with self._span("repro/fetch_tokens"):
            nxt = int(self.sampler(sub, logits[0, -1]))
        req.out_tokens.append(nxt)
        m.inc("tokens_sampled")
        if len(req.out_tokens) >= req.max_new_tokens:
            # prefill-only request (max_new_tokens=1, e.g. the spiking-ViT
            # classification workload): the admission sample is the whole
            # response — finish here instead of seating the row and
            # burning a decode tick on it
            req.done = True
            m.inc("requests_finished")
            if self.paged:
                self._release_pages(slot)
            self._admit_finished.append(req)
            self._trace(
                "admit", uid=req.uid, row=slot,
                prompt_len=len(req.prompt), wait_ticks=wait,
            )
            self._trace(
                "finish", uid=req.uid, row=slot,
                tokens=len(req.out_tokens), reason="max_new_tokens",
            )
            return
        self._last_token[id(req)] = (self._ticks.value, now)
        self.active[slot] = req
        self.slot_pos[slot] = len(req.prompt)
        self.slot_seeds[slot] = np.uint32(req.seed)
        if self.paged:
            self._admit_order[req.uid] = self._admit_seq
            self._admit_seq += 1
        self._trace("admit", uid=req.uid, row=slot,
                    prompt_len=len(req.prompt), wait_ticks=wait)

    # ------------------------------------------------------------------
    # prefix sharing: lookup / registration over (seed, token-prefix) keys
    # ------------------------------------------------------------------
    def _sharable(self, req: Request) -> bool:
        """Only prompts whose prefill never wraps a sliding-window extent
        have page contents that are a pure function of the token prefix (a
        wrapped window slot's early rows hold *tail* tokens)."""
        return (
            self.share_prefix and len(req.prompt) <= self._min_seq_extent
        )

    def _prefix_keys(self, req: Request) -> list[bytes]:
        """One key per *full* prompt-prefix page: a sha256 chain over the
        request seed and the page's tokens, so key ``j`` identifies the
        whole prefix ``tokens[:(j+1)*ps]`` in O(prompt) total work (no
        quadratic re-serialisation) and collisions are cryptographically
        negligible — a false map hit would alias another request's K/V."""
        import hashlib

        ps = self.pool.page_size
        prompt = np.asarray(req.prompt, np.int32)
        keys, digest = [], np.uint32(req.seed).tobytes()
        for j in range(len(prompt) // ps):
            digest = hashlib.sha256(
                digest + prompt[j * ps:(j + 1) * ps].tobytes()
            ).digest()
            keys.append(digest)
        return keys

    def _register_prefix_pages(self, pages: list[int], keys: list[bytes]):
        """Publish a request's full prompt-prefix pages for later arrivals
        (claimed pages are already registered; ``keys`` comes from the
        admission's single :meth:`_prefix_keys` pass)."""
        for key, page in zip(keys, pages):
            if key in self._prefix_map:
                continue
            self._prefix_map[key] = page
            self._page_key[page] = key

    def _resident_prefix(self, req: Request):
        """(shared pages already resident for this request's prompt prefix,
        their keys) — the longest prefix of full prompt pages present in
        the map; claims nothing."""
        keys = self._prefix_keys(req) if self._sharable(req) else []
        shared = []
        for key in keys:
            page = self._prefix_map.get(key)
            if page is None:
                if self._cache_on and keys:
                    # the walk ended on an unregistered key: a cache-tier
                    # lookup miss (hit rate = hits / (hits + misses))
                    self.metrics.inc("cache_misses")
                break
            shared.append(page)
        return shared, keys

    def prefix_affinity(self, req: Request) -> int:
        """Resident full-prefix pages this engine could map for ``req``
        without prefilling them.  A read-only probe for replica placement
        (serving/replicas.py): unlike :meth:`_resident_prefix` it claims
        nothing and moves no cache-miss counters, so probing every replica
        leaves their books untouched."""
        if not (self.paged and self._sharable(req)):
            return 0
        if req.seed is None:
            req.seed = self.default_seed   # what submit() would set
        n = 0
        for key in self._prefix_keys(req):
            if key not in self._prefix_map:
                break
            n += 1
        return n

    def _claim_shared(self, shared: list[int], uid: int):
        for page in shared:
            if self.pool.is_cached(page):
                # revive the parked page (the pool emits cache_hit); the
                # claimant maps it exactly as if it had stayed live-shared
                self.pool.cache_claim(page)
            else:
                self.pool.incref(page)
            self.metrics.inc("shared_page_hits")
            self._trace("shared_prefix_hit", uid=uid, page=page)

    def _pool_free(self, pages) -> list[int]:
        """Release pages, parking the ones that carry a live prefix
        registration in the pool's cache tier (when enabled); returns the
        dead list to scrub — exactly like :meth:`PagePool.free`."""
        if not self._cache_on:
            return self.pool.free(pages)
        cacheable = [p for p in pages if int(p) in self._page_key]
        return self.pool.free(pages, cacheable=cacheable)

    def _alloc_reclaim(self, n: int, protect=()) -> Optional[list]:
        """``PagePool.alloc`` with cache-tier reclamation: when the free
        list is short, evict lowest-weight cached pages (scrubbed through
        the ordinary dead-list) and retry — so the scheduler reclaims from
        the cache BEFORE pausing prefills or preempting runners.  Pages in
        ``protect`` (an admission's about-to-be-claimed prefix) survive."""
        pages = self.pool.alloc(n)
        if pages is not None or not self._cache_on:
            return pages
        evicted = self.pool.cache_reclaim(n - self.pool.num_free,
                                          protect=protect)
        if not evicted:
            return None
        self._retire_dead(evicted)
        return self.pool.alloc(n)

    def _alloc_prompt_pages(self, req: Request, rows: int):
        """Claim shared prefix pages + alloc the rest for ``rows`` cache
        rows; returns ``(pages, keys, num_shared)`` — keys for the later
        registration — or None (taking nothing) if the pool is short."""
        shared, keys = self._resident_prefix(req)
        fresh = self._alloc_reclaim(pages_for_rows(rows, self.pool.page_size)
                                    - len(shared), protect=shared)
        if fresh is None:
            return None
        self._claim_shared(shared, req.uid)
        return shared + fresh, keys, len(shared)

    def _admit(self):
        """Fill free rows FCFS: per-request prefill written chunk-by-chunk
        straight into pages (paged + chunked), scattered from a slab-row
        staging cache (paged fallback), or scattered into the batch cache
        (slab) — with ``share_prefix``, prompt-prefix pages already
        resident for the same (seed, tokens) are mapped instead of
        re-allocated."""
        if self.paged and self._inflight is not None:
            if self._inflight.resume:
                # a paused resume re-prefill heads the line; it is advanced
                # (once per tick) by _resume_preempted, never here
                return
            # continue the head-of-line admission already mid-prefill; if
            # it pauses again (pool dry) nothing later may admit (FCFS)
            if not self._advance_inflight():
                return
        for slot in self._free_slots():
            if not self.queue:
                break
            if self.paged:
                # head-of-line admission: waiting (instead of skipping
                # ahead) preserves FCFS order, which is also what keeps the
                # paged schedule aligned with the slab engine's.  Prompts
                # longer than max_seq tail-keep into the slab row cache, so
                # their footprint clamps to the table span
                req = self.queue[0]
                if self._chunkable(req):
                    self._begin_chunked(req, slot)
                    if not self._advance_inflight():
                        return
                    continue
                alloc = self._alloc_prompt_pages(
                    req, min(len(req.prompt), self.max_seq)
                )
                if alloc is None:
                    break
                pages, keys, _ = alloc
                self.queue.popleft()
                logits, row_cache = self._prefill_row(req)
                self.tables.assign(slot, pages)
                self._scatter_row(slot, row_cache)
                self._register_prefix_pages(pages, keys)
            else:
                req = self.queue.popleft()
                logits, row_cache = self._prefill_row(req)
                self.cache = jax.tree.map(
                    lambda full, row, s=slot: _scatter_slot(full, row, s),
                    self.cache,
                    row_cache,
                )
            self._start(slot, req, logits)

    # ------------------------------------------------------------------
    # chunked prefill: prefix-extend chunks written directly into pages
    # ------------------------------------------------------------------
    def _chunkable(self, req: Request) -> bool:
        """Chunked prefill serves every prompt the pow2-bucketed one-shot
        path serves: prompts longer than the smallest sliding-window cache
        extent (or than ``max_seq``) would tail-keep in the slab staging
        row — a layout chunk writes cannot reproduce incrementally — so
        they keep the one-shot fallback."""
        return (
            self.paged
            and self._chunk is not None
            and 0 < len(req.prompt) <= self._min_seq_extent
        )

    def _chunk_bucket(self, s: int) -> int:
        """Pow2-bucket a partial chunk's length (clamped to the chunk size)
        so the compiled chunk signatures stay O(log prefill_chunk)."""
        from repro.attention import next_pow2

        return min(next_pow2(s), self.prefill_chunk)

    def _run_chunk(self, req: Request, c0: int, c1: int, pages: list[int],
                   *, want_logits: bool):
        """One prefix-extend call: prefill prompt[c0:c1] writing K/V
        directly into ``pages`` through a single-row block table, attending
        over the previously written pages + the chunk itself.  Pad tokens
        of a bucketed partial chunk carry position -1: they neither draw
        nor write (their page writes sink to scratch), so page rows beyond
        the chunk stay pristine."""
        from repro.attention import PAGE_ZERO, bucketed_table_width

        s = c1 - c0
        sb = self._chunk_bucket(s)
        ps = self.pool.page_size
        tokens = np.zeros((1, sb), np.int32)
        tokens[0, :s] = req.prompt[c0:c1]
        positions = np.full((1, sb), -1, np.int32)
        positions[0, :s] = np.arange(c0, c1)
        width = bucketed_table_width(c1, ps, self.pages_per_seq)
        bt = np.full((1, width), PAGE_ZERO, np.int32)
        n = min(len(pages), width)
        bt[0, :n] = pages[:n]
        arr = _dev(bt)
        cache_view = []
        for slot_d in self.cache:
            d = dict(slot_d)
            d["bt"] = jnp.broadcast_to(
                arr[None], (slot_d["pos"].shape[0],) + arr.shape
            )
            cache_view.append(d)
        if (sb, width) not in self._chunk_signatures:
            self._chunk_signatures.add((sb, width))
            self._compile_event("prefill_chunk", [sb, width])
        args = (
            self.params,
            {"tokens": _dev(tokens), "positions": _dev(positions)},
            cache_view,
            _dev(np.full((1,), c0, np.int32)),
            _dev(np.asarray([req.seed], np.uint32)),
            jnp.asarray(s - 1, jnp.int32),
        )
        # the span holds the program launch alone: operand transfers and
        # scalar conversions happen before it
        with self._span("repro/prefill_chunk_dispatch"):
            logits, self.cache = self._chunk(*args)
        self.metrics.inc("prefill_chunks_run")
        self._trace("prefill_chunk", uid=req.uid, c0=c0, c1=c1,
                    bucket=sb, width=width)
        return logits if want_logits else None

    def _begin_chunked(self, req: Request, slot: int):
        """Pop the head-of-line request and open its chunked admission:
        claim already-resident shared prefix pages now (they must survive
        while we prefill), fresh pages come per chunk."""
        self.queue.popleft()
        shared, keys = self._resident_prefix(req)
        self._claim_shared(shared, req.uid)
        self._inflight = _ChunkedPrefill(
            req, slot, list(shared), keys,
            len(shared) * self.pool.page_size,
        )
        self.metrics.inc("chunked_prefills")

    def _advance_inflight(self) -> bool:
        """Run the in-flight prefill's remaining chunks, claiming pages
        per chunk.  Pauses (returns False) when the pool is dry — the
        request resumes at the same chunk boundary once pages free up.  On
        completion the row is seated; a fresh admission samples its first
        token, a resume re-prefill replays its recorded tokens instead.
        Returns True when nothing is left in flight."""
        inf = self._inflight
        req = inf.req
        p = len(req.prompt)
        ps = self.pool.page_size
        while inf.done < p:
            c1 = min(inf.done + self.prefill_chunk, p)
            need = pages_for_rows(c1, ps)
            if need > len(inf.pages):
                fresh = self._alloc_reclaim(need - len(inf.pages))
                if fresh is None:
                    self.metrics.inc("prefill_pauses")
                    self._trace("prefill_pause", uid=req.uid, done=inf.done,
                                resume=inf.resume)
                    return False
                inf.pages.extend(fresh)
            if c1 <= inf.shared_rows and (c1 < p or inf.resume):
                # chunk fully covered by shared prefix pages: the K/V is
                # already resident (content-addressed under RNG contract
                # v2); only a fresh admission's final chunk must run, for
                # its logits (a resume's first token is already sampled)
                self.metrics.inc("prefill_chunks_skipped")
                self._trace("prefill_skip", uid=req.uid, c0=inf.done, c1=c1)
            else:
                logits = self._run_chunk(
                    req, inf.done, c1, inf.pages,
                    want_logits=c1 == p and not inf.resume,
                )
                if c1 == p and not inf.resume:
                    inf.logits = logits
            inf.done = c1
        self._inflight = None
        self.tables.assign(inf.slot, inf.pages)
        self._register_prefix_pages(inf.pages, inf.keys)
        if inf.resume:
            self._finish_resume(inf.slot, req)
        else:
            self._start(inf.slot, req, inf.logits)
        return True

    def _cancel_inflight(self):
        """Roll an in-flight prefill back (running requests outrank it):
        release every claimed page, then requeue the request at the head
        (fresh admission — it restarts from chunk 0, which cannot change
        its stream since no token was sampled yet) or put it back on the
        preempted list (resume re-prefill — its recorded tokens are
        intact, so a later resume replays the identical stream)."""
        inf = self._inflight
        self._inflight = None
        if inf.resume:
            self._preempted.append(inf.req)
        else:
            self.queue.appendleft(inf.req)
        self.metrics.inc("prefill_aborts")
        self._trace("prefill_abort", uid=inf.req.uid, done=inf.done,
                    resume=inf.resume)
        if inf.pages:
            self._retire_dead(self._pool_free(inf.pages))

    # ------------------------------------------------------------------
    # paged scheduling: scatter, growth, preemption, resume-by-replay, CoW
    # ------------------------------------------------------------------
    def _scatter_row(self, slot: int, row_cache):
        wt = self.tables.scatter_row(slot)
        self.cache = self._scatter(self.cache, row_cache, _dev(wt))

    def _retire_dead(self, dead: list[int]):
        """Post-process pages whose refcount just hit zero: retire their
        prefix registrations and scrub them to the pristine fill (so their
        next tenant's gather tail is bit-identical to fresh slab rows).
        Every ``pool.free`` caller must route its dead list through here."""
        from repro.attention import PAGE_SCRATCH

        if not dead:
            return
        for p in dead:
            key = self._page_key.pop(p, None)
            if key is not None:
                self._prefix_map.pop(key, None)
        padded = np.full((self.pages_per_seq,), PAGE_SCRATCH, np.int32)
        padded[: len(dead)] = dead
        self.cache = self._scrub(self.cache, _dev(padded))

    def _release_pages(self, slot: int):
        """Drop this row's ownership of its pages; pages still shared with
        another owner survive untouched."""
        pages = self.tables.release(slot)
        if pages:
            self._retire_dead(self._pool_free(pages))

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """LRU-of-idle victim: all active rows were last scheduled on the
        same (previous) tick, so the order degenerates to preempting the
        most recently admitted request first (vLLM-style lowest priority)."""
        rows = [r for r in self.active if r != exclude]
        if not rows:
            return None
        return max(rows, key=lambda r: self._admit_order[self.active[r].uid])

    def _preempt(self, slot: int):
        """Release the victim's pages AND its row.  The request resumes in
        whatever row is free at resume time (replay is row-invariant under
        the request-addressed RNG, so migration cannot change its stream)."""
        req = self.active.pop(slot)
        self._release_pages(slot)
        self._drop_draft(slot)
        self._last_row[req.uid] = slot
        self._preempted.append(req)
        self.metrics.inc("preemptions")
        self._trace("preempt", uid=req.uid, row=slot,
                    tokens=len(req.out_tokens))

    def _alloc_one_or_preempt(self, exclude: int) -> Optional[list[int]]:
        """One fresh page, rolling back the in-flight chunked admission
        first (it has sampled nothing yet, so it is the cheapest victim),
        then preempting active victims (newest admission first); None only
        if no victim remains."""
        while True:
            page = self._alloc_reclaim(1)
            if page is not None:
                return page
            if self._inflight is not None:
                self._cancel_inflight()
                continue
            victim = self._pick_victim(exclude=exclude)
            if victim is None:
                return None
            self._preempt(victim)

    def _grow_pages(self):
        """Ensure every active row has a page under its next write offset,
        preempting (newest-admitted first) when the pool runs dry.  Oldest
        admissions grow first so they are never starved by newcomers."""
        ps = self.pool.page_size
        order = sorted(
            self.active, key=lambda r: self._admit_order[self.active[r].uid]
        )
        for slot in order:
            if slot not in self.active:  # preempted by an earlier iteration
                continue
            # over-long prompts tail-keep into max_seq rows (and finish on
            # their first tick, as in the slab engine) — never grow past
            # the block-table span
            col = min(int(self.slot_pos[slot]), self.max_seq - 1) // ps
            while slot in self.active and not self.tables.has_col(slot, col):
                page = self._alloc_one_or_preempt(exclude=slot)
                if page is None:  # pragma: no cover - pool sizing guards
                    raise RuntimeError(
                        "page pool exhausted by a single request; "
                        "num_pages is too small for max_seq"
                    )
                self.tables.append(slot, page[0])

    def _cow_guard(self, spec_upto: Optional[dict] = None):
        """Copy-on-write: before a decode tick, every page any active row is
        about to write must be privately owned.

        A row's tick writes column ``pos // ps`` of global layers and the
        *rolled* column ``(pos % window_extent) // ps`` of sliding-window
        layers — the latter is how a write lands in a shared prompt-prefix
        page (window wrap).  A speculative verify chunk widens the write
        span: ``spec_upto`` maps slot -> highest position the chunk writes,
        and every column in [pos, upto] is guarded.  Shared pages
        (refcount > 1) are copied to a fresh page first (byte-identical, so
        gathers are unchanged); a still-registered page with a single owner
        just retires its prefix registration, since its content is about to
        stop matching the key.
        """
        if not (self.paged and self.share_prefix):
            return
        ps = self.pool.page_size
        for slot in sorted(self.active):
            pgs = self.tables.pages.get(slot)
            if not pgs:
                continue
            pos = int(self.slot_pos[slot])
            hi = spec_upto.get(slot, pos) if spec_upto else pos
            cols = set()
            for ext in self._slot_extents:
                for p in range(pos, hi + 1):
                    r = min(p, self.max_seq - 1) if ext >= self.max_seq else p % ext
                    cols.add(r // ps)
            for col in sorted(cols):
                if slot not in self.active:
                    break
                pgs = self.tables.pages.get(slot, [])
                if col >= len(pgs):
                    continue
                page = pgs[col]
                if self.pool.ref_count(page) > 1:
                    fresh = self._alloc_one_or_preempt(exclude=slot)
                    if fresh is None:  # pragma: no cover - pool sizing
                        raise RuntimeError(
                            "page pool exhausted during copy-on-write; "
                            "num_pages is too small"
                        )
                    self.cache = self._copy(
                        self.cache,
                        jnp.asarray(page, jnp.int32),
                        jnp.asarray(fresh[0], jnp.int32),
                    )
                    self.tables.replace(slot, col, fresh[0])
                    # drops our ref; the page usually survives with its
                    # co-owners, but the alloc above may have preempted the
                    # last of them — a dead page must be scrubbed and its
                    # registration retired like any other release
                    self._retire_dead(self._pool_free([page]))
                    self.metrics.inc("cow_copies")
                    self._trace("cow_copy", uid=self.active[slot].uid,
                                row=slot, src=page, dst=fresh[0], col=col)
                elif page in self._page_key:
                    # sole owner about to write: retire the cache entry
                    self._prefix_map.pop(self._page_key.pop(page), None)

    def _sync_tables(self, spec_upto: Optional[dict] = None):
        """Rebuild the block-table leaves the decode step reads this tick.

        Every impl gets a pow2-bucketed span just wide enough for the
        longest active request (widened to the speculative verify span via
        ``spec_upto``, slot -> highest written position): position masking
        makes all backends — spiking included, since RNG contract v2 keys
        draws by absolute position — extent-invariant, so the decode
        computation never materialises a max_seq-extent tensor (recompiles
        are bounded by log2(pages_per_seq))."""
        from repro.attention import bucketed_table_width

        ps = self.pool.page_size
        rows = 1
        for slot in self.active:
            r = int(self.slot_pos[slot])
            if spec_upto:
                r = max(r, spec_upto.get(slot, r))
            rows = max(rows, r + 1)
        w = bucketed_table_width(rows, ps, self.pages_per_seq)
        if w not in self._table_widths:
            self._table_widths.add(w)
            self._compile_event("decode", w)
        arr = _dev(self.tables.as_array(w))
        for slot_d in self.cache:
            steps = slot_d["pos"].shape[0]
            slot_d["bt"] = jnp.broadcast_to(arr[None], (steps,) + arr.shape)

    def _decode_tick(self, tokens: np.ndarray):
        """One fused decode step over all rows for the given next tokens.

        Every host array crosses the dispatch boundary through ``_dev``
        (copies): dispatch is async and the scheduler mutates slot_pos /
        slot_seeds / tables right after dispatch on replay ticks.
        """
        positions = self.slot_pos[:, None].astype(np.int32)
        batch = {
            "tokens": _dev(tokens),
            "positions": _dev(positions),
        }
        args = (self.params, batch, self.cache,
                _dev(self.slot_pos))                 # per-row write offsets
        if self._seeded:
            args += (_dev(self.slot_seeds),)
        with self._span("repro/decode_dispatch"):
            logits, self.cache = self._decode(*args)
        return logits

    def _replay(self, slot: int, req: Request):
        """Re-derive a resumed request's decode-time cache rows by feeding
        its recorded tokens back through the decode step (logits discarded).

        Each replayed tick is bit-identical to the original one — same
        seed, same positions — in whatever row the request resumed
        (request-addressed RNG).  Other rows are row-parallel throughout:
        their replayed "write" deposits the same k/v their next genuine
        tick will rewrite at the same offset (or lands on the scratch page
        for idle rows), so their state is untouched; writes that would land
        in shared pages are diverted by the CoW guard exactly as a genuine
        tick would.  No sampler keys are consumed.

        Returns False if the request was itself preempted mid-replay (the
        CoW guard's page hunt may pick it as a victim, and a chunked
        resume's replay region grows from the free list only — when it
        runs dry the resume re-preempts itself rather than evicting a
        running request): its pages are already released and it is back on
        the preempted list with its tokens intact, so the caller must not
        activate it further."""
        ps = self.pool.page_size if self.paged else 0
        for tok in req.out_tokens[:-1]:
            if self.paged:
                # chunked resumes claim only their prompt pages up front;
                # the replayed growth region is granted per page here.
                # Free-list (+ cache reclamation) only — a resume must
                # never evict a running request (the old full-footprint
                # grant never did either)
                col = min(int(self.slot_pos[slot]), self.max_seq - 1) // ps
                while not self.tables.has_col(slot, col):
                    page = self._alloc_reclaim(1)
                    if page is None:
                        self._abort_resume(slot, req)
                        return False
                    self.tables.append(slot, page[0])
            tokens = np.zeros((self.b, 1), np.int32)
            for r2, rq2 in self.active.items():
                if r2 != slot and rq2.out_tokens:
                    tokens[r2, 0] = rq2.out_tokens[-1]
            tokens[slot, 0] = tok
            self._cow_guard()
            if self.active.get(slot) is not req:
                return False
            self._sync_tables()
            self._decode_tick(tokens)
            self.slot_pos[slot] += 1
            self.metrics.inc("replay_steps")
        return True

    def _abort_resume(self, slot: int, req: Request):
        """Re-preempt a resume that ran out of free pages mid-replay: its
        work is dropped (replay is pure recomputation) and it retries once
        pages free up, with its recorded tokens — hence its stream —
        untouched."""
        del self.active[slot]
        self._release_pages(slot)
        self._drop_draft(slot)
        self._last_row[req.uid] = slot
        self._preempted.append(req)
        self.metrics.inc("preemptions")
        self._trace("preempt", uid=req.uid, row=slot,
                    tokens=len(req.out_tokens), during_replay=True)

    def _finish_resume(self, slot: int, req: Request):
        """Seat a re-prefilled request back into a row and replay its
        recorded tokens (shared tail of the one-shot and chunked resume
        paths; no token is sampled — the stream is already decided)."""
        self.active[slot] = req
        self.slot_pos[slot] = len(req.prompt)
        self.slot_seeds[slot] = np.uint32(req.seed)
        self._trace("resume", uid=req.uid, row=slot,
                    tokens=len(req.out_tokens))
        prev = self._last_row.pop(req.uid, slot)
        if slot != prev:
            self.metrics.inc("migrations")
            self._trace("migrate", uid=req.uid, row=slot, from_row=prev)
        if self._replay(slot, req):
            self.metrics.inc("resumes")
            self._trace("replay", uid=req.uid, row=slot,
                        steps=len(req.out_tokens) - 1)

    def _resume_preempted(self):
        """Resume preempted requests (oldest admission first) into free
        rows.  Chunkable prompts route through the same per-chunk
        claim/pause/rollback machinery as admission (``_ChunkedPrefill``
        with ``resume=True``): pages are claimed chunk by chunk, a dry
        pool pauses the re-prefill at a chunk boundary instead of blocking
        until the full footprint fits, and the replay growth region is
        granted per page during :meth:`_replay`.  Non-chunkable prompts
        keep the one-shot path: full current footprint up front, bucketed
        prefill into a slab row, scatter, replay."""
        if self._inflight is not None and self._inflight.resume:
            # continue the head-of-line resume already mid-re-prefill; if
            # it pauses again nothing later may resume or admit (FCFS)
            if not self._advance_inflight():
                return
        if not self._preempted:
            return
        if self._inflight is not None:
            return  # a paused *admission* heads the line; resumes wait
        free = self._free_slots()
        for req in sorted(
            list(self._preempted),
            key=lambda r: self._admit_order[r.uid],
        ):
            if not free:
                break
            if self._chunkable(req):
                self._preempted.remove(req)
                slot = free.pop(0)
                shared, keys = self._resident_prefix(req)
                self._claim_shared(shared, req.uid)
                self._inflight = _ChunkedPrefill(
                    req, slot, list(shared), keys,
                    len(shared) * self.pool.page_size, resume=True,
                )
                if not self._advance_inflight():
                    return  # paused: FCFS — nothing may resume past it
                continue
            rows = min(len(req.prompt) + len(req.out_tokens) - 1,
                       self.max_seq)
            alloc = self._alloc_prompt_pages(req, rows)
            if alloc is None:
                break  # oldest first: later arrivals keep waiting too
            pages, keys, _ = alloc
            self._preempted.remove(req)
            slot = free.pop(0)
            self.tables.assign(slot, pages)
            logits, row_cache = self._prefill_row(req)
            del logits  # first token sampled at original admission
            self._scatter_row(slot, row_cache)
            self._register_prefix_pages(pages, keys)
            self._finish_resume(slot, req)

    # ------------------------------------------------------------------
    # self-speculative decode (draft k tokens, verify in ONE prefix-extend)
    # ------------------------------------------------------------------
    def _init_draft(self, draft: DraftConfig):
        """Build the draft model + its private KV state (slab row block or
        small paged pool) and register the speculative metrics."""
        if draft.k < 1:
            raise ValueError(f"DraftConfig.k must be >= 1, got {draft.k}")
        if not (self._seeded and self._has_logits_at):
            raise ValueError(
                "speculative decode requires a model whose decode_step "
                "accepts seeds= and logits_at= (the verify prefix-extend "
                "returns logits at every drafted position); this model "
                "does not"
            )
        if self._min_seq_extent < self.max_seq:
            raise ValueError(
                "speculative decode is incompatible with sliding-window "
                "layers: a rejected verify chunk's rolled writes would "
                "have destroyed window history the re-decode needs "
                f"(smallest cache extent {self._min_seq_extent} < "
                f"max_seq {self.max_seq})"
            )
        if draft.model is not None:
            dmodel = draft.model
        else:
            cfg = getattr(self.model, "cfg", None)
            if cfg is None:
                raise ValueError(
                    "cannot derive a draft model (target exposes no .cfg); "
                    "pass DraftConfig(model=..., params=...) explicitly"
                )
            from repro.configs import with_overrides
            from repro.models import build_model

            ov: dict = {}
            if draft.impl is not None:
                ov["attention__impl"] = draft.impl
                ov["attention__backend"] = "auto"
                if draft.impl == "ann":
                    # ann has no spike planes; packed storage is ssa-only
                    ov["attention__spike_storage"] = "dense"
                if draft.time_steps is not None:
                    ov["attention__ssa_time_steps"] = int(draft.time_steps)
            else:
                if cfg.attention.impl not in ("ssa", "spikformer"):
                    raise ValueError(
                        "the reduced-time-step self-draft needs a spiking "
                        f"target (impl ssa/spikformer), got "
                        f"{cfg.attention.impl!r}; set DraftConfig.impl or "
                        "DraftConfig.model instead"
                    )
                t = (int(draft.time_steps) if draft.time_steps is not None
                     else max(1, cfg.attention.ssa_time_steps // 2))
                ov["attention__ssa_time_steps"] = t
            # memoise derived drafts on the target model instance: engines
            # over the same target share the draft's jit cache (tests and
            # benchmarks build many engines per model)
            dcache = self.model.__dict__.setdefault("_draft_models", {})
            dkey = tuple(sorted(ov.items()))
            if dkey not in dcache:
                dcache[dkey] = build_model(with_overrides(cfg, **ov))
            dmodel = dcache[dkey]
        dparams = inspect.signature(dmodel.decode_step).parameters
        if "seeds" not in dparams or "logits_at" not in dparams:
            raise ValueError(
                "the draft model's decode_step must accept seeds= and "
                "logits_at= (catch-up runs as a prefix-extend chunk)"
            )
        self._draft_model = dmodel
        if draft.params is not None:
            self._draft_params = draft.params
            if self._tp_rules is not None:
                self._draft_params = jax.device_put(
                    draft.params,
                    jax.sharding.NamedSharding(
                        self.mesh, jax.sharding.PartitionSpec()
                    ),
                )
        else:
            self._draft_params = self.params
        self.spec_k = int(draft.k)
        # own keys, so draft programs are named apart from the target's
        # (``serve_draft_decode_seeded``, ``serve_draft_chunk``) even when
        # the draft is the target model itself
        self._draft_decode = self._jit_model(
            dmodel, "draft_decode_seeded",
            lambda: lambda p, batch, cache, idx, seeds: dmodel.decode_step(
                p, batch, cache, idx, seeds=seeds
            ),
        )
        self._draft_chunk = self._jit_model(
            dmodel, "draft_chunk",
            lambda: lambda p, batch, cache, idx, seeds, last:
                dmodel.decode_step(
                    p, batch, cache, idx, seeds=seeds, logits_at=last
                ),
        )
        # per-row draft cache frontier: positions [0, _draft_pos) hold valid
        # draft KV; -1 = cold (no draft state, full catch-up on first use)
        self._draft_pos = np.full(self.b, -1, np.int32)
        # adaptive throttling state (see DraftConfig.adaptive): EMA of the
        # accept rate, current per-row k ceiling, ticks until the next probe
        self.spec_adaptive = bool(draft.adaptive)
        if self.spec_adaptive:
            if not 0.0 < draft.accept_floor < 1.0:
                raise ValueError(
                    f"DraftConfig.accept_floor must be in (0, 1), "
                    f"got {draft.accept_floor}")
            if not 0.0 < draft.ema_alpha <= 1.0:
                raise ValueError(
                    f"DraftConfig.ema_alpha must be in (0, 1], "
                    f"got {draft.ema_alpha}")
            if draft.probe_period < 1:
                raise ValueError(
                    f"DraftConfig.probe_period must be >= 1, "
                    f"got {draft.probe_period}")
        self._spec_ema = np.ones(self.b, np.float64)
        self._spec_cur_k = np.full(self.b, self.spec_k, np.int32)
        self._spec_cooldown = np.zeros(self.b, np.int32)
        m = self.metrics
        for name in ("spec_ticks", "draft_dispatches", "verify_dispatches",
                     "spec_drafted_tokens", "spec_accepted_tokens",
                     "spec_rejected_tokens", "spec_throttled"):
            m.counter(name)
        for name in ("accepted_len", "phase_draft_s", "phase_verify_s"):
            m.histogram(name)
        self._spec_widths: set = set()          # verify compile signatures
        self._draft_widths: set[int] = set()    # draft table-width sigs
        self._draft_chunk_signatures: set = set()
        if self.paged:
            from repro.attention import NUM_RESERVED_PAGES

            from .paging import BlockTables, PagePool

            for name in ("draft_pages_granted", "draft_pages_released",
                         "draft_pages_retired"):
                m.counter(name)
            m.gauge("draft_pages_used")
            ps = self.pool.page_size
            dn = (draft.num_pages if draft.num_pages is not None
                  else NUM_RESERVED_PAGES + self.b * self.pages_per_seq)
            self.draft_pool = PagePool(dn, ps,
                                       on_event=self._draft_pool_event)
            self.draft_tables = BlockTables(self.b, self.pages_per_seq)
            self._draft_cache = self._place_cache(dmodel.init_cache(
                self.b, self.max_seq, layout="paged",
                num_pages=dn, page_size=ps,
            ))
        else:
            self.draft_pool = None
            self.draft_tables = None
            self._draft_cache = self._place_cache(
                dmodel.init_cache(self.b, self.max_seq))

    def _draft_pool_event(self, kind: str, **data):
        """Draft PagePool hook: separate counters, ``pool="draft"`` trace
        tag (the fuzz invariants filter main-pool accounting on it)."""
        m = self.metrics
        if kind == "page_grant":
            m.inc("draft_pages_granted", len(data["pages"]))
        elif kind == "page_release":
            m.inc("draft_pages_released", len(data["pages"]))
            m.inc("draft_pages_retired", len(data["dead"]))
        self._trace(kind, pool="draft", **data)

    def _scrub_draft(self, dead: list[int]):
        """Scrub recycled draft pages to the pristine fill (their next
        tenant's gather tail must look never-used, exactly as the target
        pool's :meth:`_retire_dead` guarantees)."""
        from repro.attention import PAGE_SCRATCH

        if not dead:
            return
        padded = np.full((self.pages_per_seq,), PAGE_SCRATCH, np.int32)
        padded[: len(dead)] = dead
        self._draft_cache = self._scrub(self._draft_cache, _dev(padded))

    def _drop_draft(self, slot: int):
        """Forget a row's draft state (preempt / finish / abort): the
        frontier resets to cold and — paged — its draft pages go home.
        Draft KV is pure recomputation, so dropping it never affects the
        committed stream; the row just pays a catch-up chunk next time."""
        if self._draft_model is None:
            return
        self._draft_pos[slot] = -1
        # the row's next occupant starts optimistic (full k, fresh EMA)
        self._spec_ema[slot] = 1.0
        self._spec_cur_k[slot] = self.spec_k
        self._spec_cooldown[slot] = 0
        if self.paged:
            pages = self.draft_tables.release(slot)
            if pages:
                self._scrub_draft(self.draft_pool.free(pages))

    def _sync_draft_tables(self, rows: int):
        """Rebuild the draft cache's block-table leaves wide enough for
        ``rows`` written rows (pow2-bucketed like the target's)."""
        from repro.attention import bucketed_table_width

        ps = self.draft_pool.page_size
        w = bucketed_table_width(max(rows, 1), ps, self.pages_per_seq)
        if w not in self._draft_widths:
            self._draft_widths.add(w)
            self._compile_event("draft_decode", w)
        arr = _dev(self.draft_tables.as_array(w))
        for slot_d in self._draft_cache:
            steps = slot_d["pos"].shape[0]
            slot_d["bt"] = jnp.broadcast_to(arr[None], (steps,) + arr.shape)

    def _claim_draft_pages(self, slot: int, rows: int) -> bool:
        """Grow row ``slot``'s draft allocation to cover ``rows`` written
        rows — free list only (speculation never preempts).  Returns False
        (taking nothing extra) when the draft pool is short."""
        need = pages_for_rows(min(rows, self.max_seq), self.draft_pool.page_size)
        have = self.draft_tables.num_pages(slot)
        if need <= have:
            return True
        fresh = self.draft_pool.alloc(need - have)
        if fresh is None:
            return False
        if have == 0:
            self.draft_tables.assign(slot, fresh)
        else:
            for p in fresh:
                self.draft_tables.append(slot, p)
        return True

    def _draft_catchup(self, slot: int, req: Request):
        """Advance a row's draft cache frontier to the target's position in
        one prefix-extend chunk over its already-committed tokens (logits
        discarded).  RNG contract v2 keys every draw by absolute position,
        so the chunk writes exactly the rows a token-by-token draft decode
        would have."""
        from repro.attention import next_pow2

        p0 = int(self.slot_pos[slot])
        d0 = max(int(self._draft_pos[slot]), 0)
        if d0 >= p0:
            return
        hist = list(req.prompt) + list(req.out_tokens)
        s = p0 - d0
        sb = min(next_pow2(s), self.max_seq)
        tokens = np.zeros((self.b, sb), np.int32)
        positions = np.full((self.b, sb), -1, np.int32)
        tokens[slot, :s] = hist[d0:p0]
        positions[slot, :s] = np.arange(d0, p0, dtype=np.int32)
        # non-participating rows write at their first *stale* draft offset
        # (the width-1 write path has no pad-drop; wider chunks sink pads
        # to scratch / drop them, so this only matters when sb == 1)
        idx = np.clip(self._draft_pos, 0, self.max_seq - 1).astype(np.int32)
        idx[slot] = d0
        if self.paged:
            self._sync_draft_tables(max(p0, int(idx.max()) + 1))
            tw = self._draft_cache[0]["bt"].shape[-1]
        else:
            tw = 0
        sig = (sb, tw)
        if sig not in self._draft_chunk_signatures:
            self._draft_chunk_signatures.add(sig)
            self._compile_event("draft_catchup", sig)
        args = (
            self._draft_params,
            {"tokens": _dev(tokens), "positions": _dev(positions)},
            self._draft_cache, _dev(idx), _dev(self.slot_seeds),
            jnp.asarray(0, jnp.int32),
        )
        with self._span("repro/draft_dispatch"):
            logits, self._draft_cache = self._draft_chunk(*args)
        del logits
        self.metrics.inc("draft_dispatches")
        self._draft_pos[slot] = p0

    def _spec_draft(self, k_row: np.ndarray) -> dict:
        """Propose up to ``k_row[slot]`` draft tokens per active row with
        greedy token-by-token draft decode; returns {slot: [tokens]}.

        Rows whose draft-page claim comes up short draft fewer (or zero)
        tokens this tick — speculation never preempts anyone.  The verify
        + correction token still advances every row, so a starved tick
        degrades to plain decode, not a stall."""
        proposals: dict[int, list[int]] = {}
        live: dict[int, int] = {}       # slot -> last fed token
        catchups = 0
        for slot in sorted(self.active):
            if k_row[slot] <= 0:
                k_row[slot] = 0
                continue
            req = self.active[slot]
            p0 = int(self.slot_pos[slot])
            if self.paged and not self._claim_draft_pages(
                    slot, p0 + int(k_row[slot])):
                fit = (self.draft_tables.num_pages(slot)
                       * self.draft_pool.page_size - p0)
                k_row[slot] = max(0, min(int(k_row[slot]), fit))
                if k_row[slot] == 0:
                    continue
            if int(self._draft_pos[slot]) < p0:
                catchups += 1
                self._draft_catchup(slot, req)
            live[slot] = req.out_tokens[-1]
            proposals[slot] = []
        kmax = max((int(k_row[s]) for s in live), default=0)
        for i in range(kmax):
            rows = [s for s in live if int(k_row[s]) > i]
            if not rows:
                break
            tokens = np.zeros((self.b, 1), np.int32)
            positions = np.full((self.b, 1), -1, np.int32)
            idx = np.clip(self._draft_pos, 0, self.max_seq - 1).astype(
                np.int32)
            for s in live:
                p0_s = int(self.slot_pos[s])
                if int(k_row[s]) > i:
                    tokens[s, 0] = live[s] if i == 0 else proposals[s][-1]
                    positions[s, 0] = idx[s] = p0_s + i
                else:
                    idx[s] = p0_s + int(k_row[s])   # first stale offset
            if self.paged:
                self._sync_draft_tables(int(idx.max()) + 1)
            args = (
                self._draft_params,
                {"tokens": _dev(tokens), "positions": _dev(positions)},
                self._draft_cache, _dev(idx), _dev(self.slot_seeds),
            )
            with self._span("repro/draft_dispatch"):
                logits, self._draft_cache = self._draft_decode(*args)
            self.metrics.inc("draft_dispatches")
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            for s in rows:
                proposals[s].append(int(nxt[s]))
                self._draft_pos[s] = int(self.slot_pos[s]) + i + 1
        proposed = sum(len(v) for v in proposals.values())
        self.metrics.inc("spec_drafted_tokens", proposed)
        self._trace(
            "draft", proposed=proposed, catchups=catchups,
            rows=sorted([s, len(proposals.get(s, ()))] for s in self.active),
        )
        return {s: v for s, v in proposals.items() if v}

    def _spec_stage(self, proposals: dict):
        """Grow the target's pages over each row's speculative span (free
        list only — on a dry pool the row's proposal is truncated to what
        its pages can hold), run the CoW guard + table sync over the
        widened write span, and build the verify chunk's host arrays.

        Token ``j`` of row ``slot``'s chunk is the last committed token
        (j=0) followed by its draft proposals, at positions ``p0..p0+k`` —
        the verify prefix-extend writes their KV and returns logits at
        every position, so ``logits[:, j]`` scores position ``p0+j+1``'s
        token under the *target* model."""
        if self.paged:
            ps = self.pool.page_size
            for slot in sorted(proposals):
                p0 = int(self.slot_pos[slot])
                while proposals[slot]:
                    col = (p0 + len(proposals[slot])) // ps
                    if self.tables.has_col(slot, col):
                        break
                    page = self._alloc_reclaim(1)
                    if page is None:
                        fit = self.tables.num_pages(slot) * ps - 1 - p0
                        del proposals[slot][max(0, fit):]
                        continue
                    self.tables.append(slot, page[0])
                if not proposals[slot]:
                    del proposals[slot]
            upto = {
                s: int(self.slot_pos[s]) + len(proposals.get(s, ()))
                for s in self.active
            }
            self._cow_guard(upto)
            # the CoW page hunt may have preempted proposal rows
            for s in list(proposals):
                if s not in self.active:
                    del proposals[s]
            self._sync_tables(upto)
        width = 1 + max((len(v) for v in proposals.values()), default=0)
        tokens = np.zeros((self.b, width), np.int32)
        positions = np.full((self.b, width), -1, np.int32)
        idx = self.slot_pos.astype(np.int32).copy()
        for slot, req in self.active.items():
            row = [req.out_tokens[-1]] + proposals.get(slot, [])
            p0 = int(self.slot_pos[slot])
            tokens[slot, : len(row)] = row
            positions[slot, : len(row)] = np.arange(
                p0, p0 + len(row), dtype=np.int32
            )
            idx[slot] = p0
        return width, tokens, positions, idx

    def _spec_verify(self, width, tokens, positions, idx):
        """One target prefix-extend over every row's ``[last committed,
        drafts...]`` chunk; returns ``(B, width, V)`` logits."""
        tw = self.cache[0]["bt"].shape[-1] if self.paged else 0
        if width > 1:
            # width == 1 is the plain decode signature _sync_tables tracks
            sig = (width, tw)
            if sig not in self._spec_widths:
                self._spec_widths.add(sig)
                self._compile_event("verify", sig)
        self._trace("verify", width=width, active=len(self.active))
        args = (
            self.params,
            {"tokens": _dev(tokens), "positions": _dev(positions)},
            self.cache, _dev(idx), _dev(self.slot_seeds),
        )
        with self._span("repro/verify_dispatch"):
            logits, self.cache = self._decode(*args)
        self.metrics.inc("verify_dispatches")
        return logits

    def _rewind_spec(self, slot: int, p0: int, drafted: int):
        """Roll back the rejected suffix of a row's speculative span.

        The *target* cache needs no data rewind: every stale entry beyond
        the new ``slot_pos`` stores its own position, so queries below it
        mask it out, and the genuine decode of a rewound position rewrites
        its row before anything attends (write-before-attend) — RNG
        contract v2 makes that re-decode bit-identical.  Only the paged
        block-table *extents* roll back so unbacked tail pages return to
        the pool.  The draft frontier drops to the last position whose
        draft KV still matches the committed stream."""
        pos = int(self.slot_pos[slot])
        cur = int(self._draft_pos[slot])
        if cur >= 0:
            self._draft_pos[slot] = min(cur, pos, p0 + drafted)
        if not self.paged:
            return
        ps = self.pool.page_size
        tail = self.tables.truncate(slot, pos // ps + 1)
        if tail:
            self._retire_dead(self._pool_free(tail))
        d = int(self._draft_pos[slot])
        if d >= 0 and self.draft_tables.num_pages(slot):
            dtail = self.draft_tables.truncate(slot, d // ps + 1)
            if dtail:
                self._scrub_draft(self.draft_pool.free(dtail))

    def _spec_commit(self, proposals: dict, width: int, logits):
        """Accept the longest draft prefix the target's sampler agrees
        with, commit it plus one correction/bonus token, rewind the rest.

        One sampler key per tick (as in plain decode), folded per chunk
        position: ``cand[:, j]`` is the token the target would sample at
        position ``p0+j+1``.  Greedy ignores the key entirely, so the
        committed stream is token-identical to non-speculative decode;
        keyed samplers commit only sampler draws from target logits
        (distribution-exact), with a different key schedule."""
        m = self.metrics
        self.key, sub = jax.random.split(self.key)
        with self._span("repro/fetch_tokens"):
            cand = np.stack(
                [np.asarray(self.sampler(jax.random.fold_in(sub, j),
                                         logits[:, j]))
                 for j in range(width)],
                axis=1,
            )
        now = time.perf_counter()
        tick = self._ticks.value
        finished: list[Request] = []
        for slot, req in list(self.active.items()):
            p0 = int(self.slot_pos[slot])
            props = proposals.get(slot, [])
            kr = len(props)
            accepted = 0
            committed: list[int] = []
            for j in range(kr + 1):
                tok = int(cand[slot, j])
                committed.append(tok)
                if j < kr and props[j] == tok:
                    accepted += 1
                else:
                    break
            rejected = kr - accepted
            if kr:  # rows that drafted nothing just ran a plain decode
                m.observe("accepted_len", accepted)
                m.inc("spec_accepted_tokens", accepted)
                self._trace("accept", uid=req.uid, row=slot, drafted=kr,
                            accepted=accepted, committed=len(committed))
            if rejected:
                m.inc("spec_rejected_tokens", rejected)
                self._trace("reject", uid=req.uid, row=slot,
                            rejected=rejected, at=p0 + accepted + 1)
            if self.spec_adaptive:
                self._spec_update(slot, kr, accepted)
            reason = None
            for tok in committed:
                req.out_tokens.append(tok)
                m.inc("tokens_sampled")
                last = self._last_token.get(id(req))
                if last is not None:
                    m.observe("intertoken_ticks", tick - last[0])
                    m.observe("intertoken_wall_s", now - last[1])
                self._last_token[id(req)] = (tick, now)
                self.slot_pos[slot] += 1
                if tok in req.eos_ids():
                    reason = "eos"
                elif len(req.out_tokens) >= req.max_new_tokens:
                    reason = "max_new_tokens"
                elif self.slot_pos[slot] >= self.max_seq - 1:
                    reason = "max_seq"
                if reason is not None:
                    break  # later tokens were never generated (identity)
            if reason is None:
                self._rewind_spec(slot, p0, kr)
                continue
            req.done = True
            finished.append(req)
            del self.active[slot]
            self._drop_draft(slot)
            self._last_token.pop(id(req), None)
            m.inc("requests_finished")
            if self.paged:
                self._release_pages(slot)
                self._admit_order.pop(req.uid, None)
                self._last_row.pop(req.uid, None)
            self._trace("finish", uid=req.uid, row=slot,
                        tokens=len(req.out_tokens), reason=reason)
        m.inc("spec_ticks")
        return finished

    def _spec_update(self, slot: int, kr: int, accepted: int):
        """Adaptive throttling (``DraftConfig.adaptive``): fold this tick's
        accept rate into the row's EMA; an EMA below ``accept_floor``
        shrinks the row's k ceiling one step (down to 0 = plain decode
        ticks), and a throttled row probes one step wider every
        ``probe_period`` spec ticks — with its EMA lifted back to the floor
        so one good probe keeps the wider k."""
        floor = self.draft.accept_floor
        if kr > 0:
            a = self.draft.ema_alpha
            self._spec_ema[slot] = ((1.0 - a) * self._spec_ema[slot]
                                    + a * (accepted / kr))
        if (kr > 0 and self._spec_ema[slot] < floor
                and self._spec_cur_k[slot] > 0):
            self._spec_cur_k[slot] -= 1
            self._spec_cooldown[slot] = self.draft.probe_period
            self.metrics.inc("spec_throttled")
        elif self._spec_cur_k[slot] < self.spec_k:
            if self._spec_cooldown[slot] > 0:
                self._spec_cooldown[slot] -= 1
            else:
                self._spec_cur_k[slot] += 1
                self._spec_cooldown[slot] = self.draft.probe_period
                self._spec_ema[slot] = max(self._spec_ema[slot], floor)

    def _spec_tick(self) -> list[Request]:
        """One speculative engine tick: draft up to k tokens per row, one
        verify prefix-extend, longest-accepted-prefix commit + rewind."""
        m = self.metrics
        k_row = np.zeros(self.b, np.int32)
        for slot, req in self.active.items():
            p0 = int(self.slot_pos[slot])
            k_row[slot] = max(0, min(
                int(self._spec_cur_k[slot]),  # == spec_k unless throttled
                req.max_new_tokens - len(req.out_tokens) - 1,
                self.max_seq - 1 - p0,
            ))
        with self._phase("draft"):
            proposals = self._spec_draft(k_row)
        if self.paged:
            m.gauge("draft_pages_used").set(self.draft_pool.num_used)
        with self._phase("host_stage"):
            width, tokens, positions, idx = self._spec_stage(proposals)
        if not self.active:
            return []  # the CoW page hunt preempted every row
        if self.paged:
            m.gauge("pages_used").set(self.pool.num_used)
        if self.tracer is not None:
            data = {
                "active": len(self.active),
                "rows": sorted([s, r.uid] for s, r in self.active.items()),
                "width": width,
            }
            if self.paged:
                data["pages_used"] = self.pool.num_used
                if self._cache_on:
                    data["cache_pages"] = self.pool.num_cached
            self._trace("decode_tick", **data)
        with self._phase("verify"):
            logits = self._spec_verify(width, tokens, positions, idx)
        tr = self.tracer
        if tr is not None and tr.sync_device:
            with self._phase("device_sync"):
                jax.block_until_ready(logits)
        with self._phase("sample"):
            self._ticks.inc()
            finished = self._spec_commit(proposals, width, logits)
        return finished

    # ------------------------------------------------------------------
    @property
    def has_pending_work(self) -> bool:
        """True while any request is queued, active, or preempted — the
        public drive-loop condition (external tick loops should not poke
        scheduler internals)."""
        return bool(
            self.queue or self.active
            or (self.paged and (self._preempted or self._inflight))
        )

    @property
    def num_prefill_compiles(self) -> int:
        """Number of distinct compiled prefill signatures this engine has
        triggered (== distinct prompt-length buckets when bucketing is on)."""
        if self._prefill is not None:
            return int(self._prefill._cache_size())
        return len(self._prefill_buckets)

    # ------------------------------------------------------------------
    def step(self) -> list[Request]:
        """One engine tick: resume / admit / grow pages / CoW, then one
        fused decode step for all rows.  Returns the requests that
        finished.

        With a tracer attached the tick is split into timed phases
        (``schedule`` / ``host_stage`` / ``dispatch`` / ``device_sync`` /
        ``sample``); untraced, the phase contexts are a shared no-op and
        the tick body is unchanged."""
        m = self.metrics
        with self._phase("schedule"):
            if self.paged:
                self._resume_preempted()
            with self._span("repro/admit"):
                self._admit()
            if self.active and self.paged:
                self._grow_pages()
                if self._draft_model is None:
                    # spec ticks rerun the guard + sync over the widened
                    # speculative write span inside _spec_stage
                    self._cow_guard()
                    self._sync_tables()
                m.gauge("pages_used").set(self.pool.num_used)
        finished0: list[Request] = []
        if self._admit_finished:
            finished0, self._admit_finished = self._admit_finished, []
        if not self.active:
            return finished0
        m.gauge("concurrency").set(len(self.active))
        m.gauge("occupancy").set(
            self.pool.num_used / max(self.pool.num_usable, 1)
            if self.paged else len(self.active) / max(self.b, 1)
        )
        if self._draft_model is not None:
            return finished0 + self._spec_tick()
        with self._phase("host_stage"):
            tokens = np.zeros((self.b, 1), np.int32)
            for slot, req in self.active.items():
                tokens[slot, 0] = req.out_tokens[-1]
        if self.tracer is not None:
            data = {
                "active": len(self.active),
                "rows": sorted([s, r.uid] for s, r in self.active.items()),
            }
            if self.paged:
                data["pages_used"] = self.pool.num_used
                if self._cache_on:
                    data["cache_pages"] = self.pool.num_cached
            self._trace("decode_tick", **data)
        # NOTE: static-shape engine uses one shared cache_index per tick via
        # per-slot positions; the cache write offset is each slot's position
        with self._phase("dispatch"):
            logits = self._decode_tick(tokens)
        tr = self.tracer
        if tr is not None and tr.sync_device:
            # separates async-dispatch cost from device execution in the
            # phase timings; numerics and token streams are unchanged
            with self._phase("device_sync"):
                jax.block_until_ready(logits)
        with self._phase("sample"):
            self._ticks.inc()
            self.key, sub = jax.random.split(self.key)
            with self._span("repro/fetch_tokens"):
                nxt = np.asarray(self.sampler(sub, logits[:, -1]))
            finished = self._commit(nxt)
        return finished0 + finished

    def _commit(self, nxt: np.ndarray) -> list[Request]:
        """Append this tick's sampled tokens, record per-token latency,
        and retire finished rows."""
        m = self.metrics
        now = time.perf_counter()
        tick = self._ticks.value
        finished: list[Request] = []
        for slot, req in list(self.active.items()):
            tok = int(nxt[slot])
            req.out_tokens.append(tok)
            m.inc("tokens_sampled")
            last = self._last_token.get(id(req))
            if last is not None:
                m.observe("intertoken_ticks", tick - last[0])
                m.observe("intertoken_wall_s", now - last[1])
            self._last_token[id(req)] = (tick, now)
            self.slot_pos[slot] += 1
            if tok in req.eos_ids():
                reason = "eos"
            elif len(req.out_tokens) >= req.max_new_tokens:
                reason = "max_new_tokens"
            elif self.slot_pos[slot] >= self.max_seq - 1:
                reason = "max_seq"
            else:
                continue
            req.done = True
            finished.append(req)
            del self.active[slot]
            self._last_token.pop(id(req), None)
            m.inc("requests_finished")
            if self.paged:
                self._release_pages(slot)
                self._admit_order.pop(req.uid, None)
                self._last_row.pop(req.uid, None)
            self._trace("finish", uid=req.uid, row=slot,
                        tokens=len(req.out_tokens), reason=reason)
        return finished

    def run_until_done(self, max_ticks: int = 10_000) -> list[Request]:
        """Drive the engine until queue + rows drain; returns finished
        requests in completion order."""
        done: list[Request] = []
        ticks = 0
        while self.has_pending_work and ticks < max_ticks:
            done.extend(self.step())
            ticks += 1
        return done

    # ------------------------------------------------------------------
    def kv_cache_nbytes(self) -> int:
        """Resident bytes of the KV cache (all leaves, all layers).

        With ``spike_storage="packed"`` the spiking K/V planes are uint32
        bit-planes (1 bit/spike) instead of f32/bf16 lanes, and with
        ``cache_layout="paged"`` this is the shared page pool — the actual
        allocation, sized by ``num_pages`` rather than
        ``num_slots * max_seq``.  The count is logical (sharding-invariant):
        a head-sharded engine reports the same total as an unsharded one;
        :meth:`kv_shard_nbytes` breaks it down per model shard."""
        return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(self.cache))

    def kv_shard_nbytes(self) -> list[int]:
        """Per-model-shard resident KV bytes (one entry per shard).

        Head-sharded payload leaves contribute ``nbytes / shards`` to each
        shard; replicated leaves (``pos``, ``bt``, non-divisible payloads)
        contribute their full size to every shard — exactly the bytes one
        device along the ``model`` axis holds."""
        shards = self.mesh_shards
        if shards == 1:
            return [self.kv_cache_nbytes()]
        from repro.distributed.sharding import (
            _leaf_name, serving_cache_leaf_spec,
        )

        per = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.cache)[0]:
            spec = serving_cache_leaf_spec(
                _leaf_name(path), leaf.ndim, self._kv_heads, shards
            )
            sharded = any(ax is not None for ax in spec)
            per += int(leaf.nbytes) // shards if sharded else int(leaf.nbytes)
        return [per] * shards

    def stats(self) -> dict:
        """Scheduler observability: a frozen snapshot (plain dict, safe to
        mutate) assembled from the metrics registry plus live queue / pool
        state.  The key set is stable per layout (tests pin the schema);
        :meth:`snapshot` adds the latency / phase histograms on top."""
        c = self.metrics.counter
        out = {
            "layout": self.layout,
            "ticks": self.steps_run,
            "active": len(self.active),
            "queued": len(self.queue),
            "queue_wait_ticks": self.queue_wait_ticks,
            "kv_cache_nbytes": self.kv_cache_nbytes(),
            "requests_submitted": c("requests_submitted").value,
            "requests_finished": c("requests_finished").value,
            "tokens_sampled": c("tokens_sampled").value,
            "compile_events": c("compile_events").value,
        }
        # sharded / replicated keys appear only when configured, so the
        # plain engine's schema (which tests pin) is untouched
        if self.mesh_shards > 1:
            out["mesh_shards"] = self.mesh_shards
            out["kv_shard_nbytes"] = self.kv_shard_nbytes()
        if self.replica_id is not None:
            out["replica"] = self.replica_id
        if self._draft_model is not None:
            out.update(
                spec_k=self.spec_k,
                spec_ticks=c("spec_ticks").value,
                draft_dispatches=c("draft_dispatches").value,
                verify_dispatches=c("verify_dispatches").value,
                spec_drafted_tokens=c("spec_drafted_tokens").value,
                spec_accepted_tokens=c("spec_accepted_tokens").value,
                spec_rejected_tokens=c("spec_rejected_tokens").value,
                spec_adaptive=self.spec_adaptive,
                spec_throttled=c("spec_throttled").value,
            )
            if self.paged:
                out.update(
                    draft_num_pages=self.draft_pool.num_pages,
                    draft_pages_used=self.draft_pool.num_used,
                    draft_pages_granted=c("draft_pages_granted").value,
                    draft_pages_released=c("draft_pages_released").value,
                    draft_pages_retired=c("draft_pages_retired").value,
                )
        if not self.paged:
            out["occupancy"] = len(self.active) / max(self.b, 1)
            return out
        out.update(
            page_size=self.pool.page_size,
            num_pages=self.pool.num_pages,
            pages_free=self.pool.num_free,
            pages_used=self.pool.num_used,
            peak_pages_used=self.peak_pages_used,
            occupancy=self.pool.num_used / max(self.pool.num_usable, 1),
            preempted_now=len(self._preempted),
            preemptions=self.preemptions,
            resumes=self.resumes,
            replay_steps=self.replay_steps,
            migrations=self.migrations,
            max_concurrency_seen=self.max_concurrency_seen,
            share_prefix=self.share_prefix,
            shared_pages_now=self.pool.num_shared,
            shared_page_hits=self.shared_page_hits,
            cow_copies=self.cow_copies,
            prefill_chunk=self.prefill_chunk,
            chunked_prefills=self.chunked_prefills,
            prefill_chunks_run=self.prefill_chunks_run,
            prefill_chunks_skipped=self.prefill_chunks_skipped,
            prefill_pauses=self.prefill_pauses,
            prefill_aborts=self.prefill_aborts,
            prefill_in_flight=self._inflight is not None,
            pages_granted=c("pages_granted").value,
            pages_shared=c("pages_shared").value,
            pages_released=c("pages_released").value,
            pages_retired=c("pages_retired").value,
        )
        if self._cache_on:
            out.update(
                prefix_cache_pages=self.prefix_cache_pages,
                cached_pages_now=self.pool.num_cached,
                cache_inserts=c("cache_inserts").value,
                cache_hits=c("cache_hits").value,
                cache_misses=c("cache_misses").value,
                cache_evictions=c("cache_evictions").value,
            )
        return out

    def snapshot(self) -> dict:
        """Full observability snapshot: :meth:`stats` plus the metrics
        registry (histogram summaries for TTFT / inter-token latency /
        queue wait / tick phases) and, when tracing, the tracer's emit
        counters.  Everything is a plain deep-copied dict."""
        out = {"stats": self.stats(), "metrics": self.metrics.snapshot()}
        if self.tracer is not None:
            out["trace"] = {
                "events_emitted": self.tracer.events_emitted,
                "events_dropped": self.tracer.events_dropped,
            }
        return out


def _scatter_slot(full: jax.Array, row: jax.Array, slot: int) -> jax.Array:
    """Write a batch-1 cache leaf into batch row ``slot`` of the full cache.

    Cache trees mix (B, ...) and (L, B, ...) leaves; the batch axis is the
    unique axis where the shapes differ (full has B, row has 1)."""
    diffs = [ax for ax in range(full.ndim) if full.shape[ax] != row.shape[ax]]
    if not diffs:  # B == 1 engine: shapes identical
        return row.astype(full.dtype)
    ax = diffs[0]
    return jax.lax.dynamic_update_slice_in_dim(
        full, row.astype(full.dtype), slot, axis=ax
    )
