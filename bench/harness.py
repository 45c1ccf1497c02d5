"""Run one benchmark cell: set up, warm up, measure, check, report.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, the limits its outputs are held to in
``bench/limits/<cell>.json``, and each metric's reader in
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a mix or a
metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import gc
import importlib.util
import itertools
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np

from . import families, traffic

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
# an answer due in the window may come this late and still count
DRAIN_LIMIT_S = 60.0

clock = time.perf_counter


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what a cell is made of
# ---------------------------------------------------------------------------
@dataclass
class Spec:
    workload: str
    cell: dict                 # the BENCHMARK.json workloads entry
    config: dict               # bench/configs/<config>.json
    mix: dict                  # bench/traffic/<traffic>.json
    limits: dict               # bench/limits/<workload>.json
    end_to_end: list           # metric entries this cell reports
    per_layer: list


def _reports(metric: dict, workload: str, e2e_names: set) -> bool:
    """A per-layer metric is read in the cells it lists, or else in every
    cell that reports the end-to-end metric it moves."""
    cells = metric.get("workloads")
    return workload in cells if cells is not None else metric["moves"] in e2e_names


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((root / "bench" / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in bm["end_to_end"]
           if m.get("workloads") is None or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _reports(m, workload, names)]
    return Spec(workload, cell, cfg, mix, limits, e2e, per_layer)


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------
@dataclass
class Record:
    planned: traffic.PlannedRequest
    req: object                # the engine's Request
    due: float                 # host clock when the request was due
    submitted: float
    times: list = field(default_factory=list)   # host clock of each token

    @property
    def done(self) -> bool:
        return bool(self.req.done)


@dataclass
class RunData:
    """What the metric readers read."""
    spec: Spec
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    t_end: float               # the last answer awaited
    judged_until: float        # tokens after this are no run's measure
    records: list              # every request submitted
    attempted: list            # the requests the window is judged by
    peaks: dict
    trace_t0: Optional[float] = None
    trace_t1: Optional[float] = None
    events: Optional[list] = None      # engine Tracer events (traced run)
    trace: Optional[object] = None     # trace_reduce.TraceSummary

    @property
    def config(self) -> dict:
        return self.spec.config

    def tokens_in(self, t0: float, t1: float):
        """(record, token index, time) of every token served in [t0, t1]."""
        for r in self.records:
            for i, t in enumerate(r.times):
                if t0 <= t <= t1:
                    yield r, i, t


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts XLA compilations (backend compiles and cache loads)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.n += 1


# ---------------------------------------------------------------------------
# serving loops
# ---------------------------------------------------------------------------
class Feeder:
    """Sends planned requests to the engine, steps it, and stamps every
    served token with the host clock when the step that made it returns."""

    def __init__(self, system, engine, uid0: int = 0):
        self.system = system
        self.engine = engine
        self.records: list[Record] = []
        self.inflight: list[Record] = []
        self.lateness: list[float] = []
        self._uid = uid0

    def submit(self, planned, due: float):
        import jax

        now = clock()
        req = self.system.request(self._uid, planned)
        self._uid += 1
        with jax.profiler.TraceAnnotation("bench/submit"):
            self.engine.submit(req)
        rec = Record(planned, req, due, now)
        self.records.append(rec)
        self.inflight.append(rec)
        self.lateness.append(now - due)
        return rec

    def step(self) -> list:
        import jax

        with jax.profiler.TraceAnnotation("bench/step"):
            finished = self.engine.step()
        now = clock()
        keep = []
        for rec in self.inflight:
            n = len(rec.req.out_tokens)
            if n > len(rec.times):
                rec.times.extend([now] * (n - len(rec.times)))
            if not rec.done:
                keep.append(rec)
        self.inflight = keep
        return finished

    def run_closed(self, plan, seconds, on_open, on_close):
        """Closed loop: each client sends its next request as soon as the
        previous one has finished.  The first round is admitted before the
        window opens; returns (t_open, t_close)."""
        # the pool repeats once spent: the same sizes in the same order
        pool = itertools.cycle(plan.requests[plan.clients:])
        for p in plan.first_round():
            self.submit(p, clock())
        self.step()
        t_open = on_open()
        t_close = t_open + seconds
        while clock() < t_close:
            for _ in self.step():
                self.submit(next(pool), clock())
        on_close()
        # the answers of requests sent in the window: step on, sending
        # nothing, until the finished ones cover the check's sample (or
        # ``DRAIN_LIMIT_S`` has passed since a traced run's trace was
        # stopped); none of it is measured
        t_drain = clock()
        while (self.inflight and clock() < t_drain + DRAIN_LIMIT_S and
               sum(len(r.req.out_tokens) for r in self.records if r.done)
               < plan.sample_tokens):
            self.step()
        return t_open, t_close

    def run_open(self, plan, seconds, on_open, on_close):
        """Open loop: every request is sent when it falls due, whatever the
        server does; after the window closes, the answers due in it are
        awaited (up to ``DRAIN_LIMIT_S``).  Returns (t_open, t_close)."""
        import jax

        pending = list(plan.requests)
        t_open = on_open()
        t_close = t_open + seconds
        closed = False
        while True:
            now = clock()
            if not closed and now >= t_close:
                on_close()
                closed = True
            while pending and t_open + pending[0].due <= now:
                p = pending.pop(0)
                self.submit(p, t_open + p.due)
            if self.engine.has_pending_work:
                self.step()
            elif pending:
                with jax.profiler.TraceAnnotation("bench/wait"):
                    time.sleep(max(0.0, t_open + pending[0].due - clock()))
            else:
                break
            if now > t_close + DRAIN_LIMIT_S:
                break
        if not closed:
            while clock() < t_close:
                time.sleep(t_close - clock())
            on_close()
        return t_open, t_close


def warm_up(system, engine, plan) -> int:
    """Serve, one at a time, requests that reach every program shape the
    plan can reach (and no other); returns how many were served."""
    r0 = plan.requests[0]
    warm = [traffic.PlannedRequest(-1, r0.prompt[:1].repeat(p), n, r0.seed, None)
            for p, n in system.warmup_prompts(plan)]
    drv = Feeder(system, engine)
    for w in warm:
        # one at a time: a decode tick's shape follows its widest row
        drv.submit(w, clock())
        while engine.has_pending_work:
            drv.step()
    return len(warm)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def sample_for_check(records, sample_tokens: int, seed: int) -> list:
    """Finished requests for the check: the longest, then others drawn from
    the seed, until ``sample_tokens`` served tokens are covered."""
    done = [r for r in records if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.planned.prompt) + len(r.req.out_tokens))
    rng = np.random.default_rng([int(seed), 0x434845])
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not longest]
    out, n = [longest], len(longest.req.out_tokens)
    for r in rest:
        if n >= sample_tokens:
            break
        out.append(r)
        n += len(r.req.out_tokens)
    return out


def reference_for(config: dict):
    """The plain reference of the configuration's family."""
    return families.load(config["family"]).Reference(config)


def served_gaps(config: dict, key, sample, others=()) -> list:
    """Gaps of the sampled requests' served tokens below the reference at
    the configuration's numerics (and of the picks of ``others`` modes)."""
    import jax

    from .reference.numerics import mode_of

    ref = reference_for(config)
    seqs = [(r.planned.prompt, list(r.req.out_tokens), r.planned.seed) for r in sample]
    kw = families.load(config["family"]).reference_kwargs(config)
    with jax.default_matmul_precision("highest"):
        return ref.served_gaps(key, seqs, mode=mode_of(config),
                               others=tuple(others), **kw)


def kv_snapshot(system, drv, limits: dict, seed: int) -> list:
    """At the window's close: the K/V spike planes of up to
    ``limits["kv_requests"]`` in-flight requests (drawn from the seed), for
    the first ``limits["kv_layers"]`` layers; none where the limits ask
    for none."""
    layers = limits.get("kv_layers", 0)
    seated = {id(r) for r in drv.engine.active.values()}
    active = [r for r in drv.inflight if id(r.req) in seated] if layers else []
    if not active:
        return []
    rng = np.random.default_rng([int(seed), 0x4B56])
    pick = rng.permutation(len(active))[:limits["kv_requests"]]
    return system.kv_snapshot(drv.engine, [active[i] for i in pick], layers)


def kv_mismatch(program: dict, reference: dict) -> np.ndarray:
    """Share of spikes that differ, per layer, between two sets of packed
    planes (K and V together)."""
    diff = np.zeros(program["ks"].shape[0])
    total = 0
    for name in ("ks", "vs"):
        x = np.bitwise_xor(program[name], reference[name])
        per_layer = x.reshape(x.shape[0], -1)
        diff += np.unpackbits(per_layer.view(np.uint8), axis=1).sum(1)
        total += per_layer.shape[1] * 32
    return diff / total


def _reading(gaps) -> dict:
    return {"widest_logit_gap": float(gaps.max()), "mean_logit_gap": float(gaps.mean()),
            "disagree_share": float((gaps > 0).mean())}


_AT_LEAST = ("checked_tokens", "kv_checked_requests")


def judge(lim: dict, failed: int, served, kv_worst, kv_requests: int):
    """The numbers compared, each beside its limit, and whether all hold.

    ``served``: gaps of the judged tokens below the reference's best
    logit; ``kv_worst``: per compared layer, the worst share of cached
    spikes that differ from the reference's (``None``: not compared)."""
    checks = {
        "unanswered": {"value": failed, "limit": 0},
        "checked_tokens": {"value": int(served.size), "limit": lim["checked_tokens_min"]},
    }
    for name, stat in (("widest_logit_gap", np.max), ("mean_logit_gap", np.mean)):
        if name in lim:
            checks[name] = {"value": float(stat(served)) if served.size else math.inf,
                            "limit": lim[name]}
    if kv_worst is not None:
        checks["kv_checked_requests"] = {"value": kv_requests, "limit": lim["kv_requests"]}
        checks["kv_spike_mismatch"] = {
            "value": float(kv_worst.max()) if kv_requests else math.inf,
            "limit": lim["kv_spike_mismatch"]}
    correct = all(v["value"] >= v["limit"] if k in _AT_LEAST else v["value"] <= v["limit"]
                  for k, v in checks.items())
    return correct, checks


def check_outputs(spec, key, sample, kv, failed: int, others=()):
    """Compare what the timed path produced with the plain reference.

    Numbers compared, each against its limit in ``spec.limits``: requests
    never answered; served tokens checked; the widest and (or) the mean
    gap of a served token below the reference's best logit, as the limits
    name them; and, where the limits ask for it, the share of cached K/V
    spikes of in-flight requests that differ from the reference's, in the
    worst of the first layers.  Each mode of ``others`` (a control, a
    planted fault) is judged the same way in the program's place: its
    first choice at each served position stands for the served token, its
    cached spikes for the engine's.  Returns (correct, checks, judged),
    ``judged`` holding for the program and each mode of ``others`` its
    ``correct``, ``checks`` and ``readings``.
    """
    from .reference.numerics import mode_of

    lim, base = spec.limits, mode_of(spec.config)
    gaps = served_gaps(spec.config, key, sample, others) if sample else []
    modes = ("program",) + tuple(others)
    served = {m: np.concatenate([g["served" if m == "program" else m] for g in gaps])
              if gaps else np.zeros(0) for m in modes}
    worst = {m: None for m in modes}
    if "kv_layers" in lim:
        kw = families.load(spec.config["family"]).reference_kwargs(spec.config)
        ref = reference_for(spec.config).kv_planes(
            key, [(tokens, rec.planned.seed) for rec, tokens, _ in kv],
            layers=lim["kv_layers"], modes=(base,) + tuple(others), **kw) if kv else {}
        worst = {m: np.zeros(lim["kv_layers"]) for m in modes}
        for i, (_, _, planes) in enumerate(kv):
            worst["program"] = np.maximum(worst["program"], kv_mismatch(planes, ref[base][i]))
            for m in others:
                worst[m] = np.maximum(worst[m], kv_mismatch(ref[m][i], ref[base][i]))
    judged = {}
    for m in modes:
        ok, checks = judge(lim, failed, served[m], worst[m], len(kv))
        readings = _reading(served[m]) if served[m].size else {}
        if worst[m] is not None:
            readings["kv_mismatch_by_layer"] = [float(x) for x in worst[m]]
        judged[m] = {"correct": bool(ok), "checks": checks, "readings": readings}
    return judged["program"]["correct"], judged["program"]["checks"], judged


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(spec: Spec, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None, others: tuple = (),
             system_hook=None, peaks: Optional[dict] = None) -> dict:
    """Set up, warm up, measure and check one cell; returns the result line.

    ``devices``: the accelerators found (``None``: the look for a chip was
    skipped).  ``others``: numerics modes (``reference.numerics``), such
    as the control, judged in the program's place (never in the
    benchmark's own runs).
    ``system_hook``: called with the built system before serving (tests
    plant faults through it).  ``peaks``: the device's peak table entry
    (default: ``bench/peaks.json`` for the device found).
    """
    import jax

    from . import system as system_mod
    from . import trace_reduce

    counter = CompileCounter()
    log(f"imports done at {clock() - t_start:.3f} s")
    system = system_mod.System(spec.config, seed)
    log(f"weights on the device at {clock() - t_start:.3f} s")
    if system_hook is not None:
        system_hook(system)
    plan = traffic.make_plan(spec.mix, vocab=system.vocab, seed=seed,
                             seconds=seconds, max_total=system.max_total)
    tracer = None
    if trace:
        from repro.obs.trace import Tracer

        tracer = Tracer(capacity=1 << 20)
    engine = system.engine(tracer=tracer)
    warm = warm_up(system, engine, plan)
    log(f"engine built and {warm} warm-up requests served at {clock() - t_start:.3f} s")
    drv = Feeder(system, engine, uid0=warm)
    sig0 = engine.stats()["compile_events"]
    state = {}
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def on_open():
        state["compiles"] = counter.n
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # spans only; no per-call tracing
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            # made after the trace starts, or it is not recorded
            state["span"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            state["span"].__enter__()
            state["trace_t0"] = clock()
        state["t_open"] = clock()
        return state["t_open"]

    def on_close():
        state["compiles_window"] = counter.n - state["compiles"]
        if trace:
            state["trace_t1"] = clock()
            state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
        state["kv"] = kv_snapshot(system, drv, spec.limits, seed)

    runner = drv.run_closed if plan.loop == "closed" else drv.run_open
    t_open, t_close = runner(plan, seconds, on_open, on_close)
    t_end = clock()
    setup_s = t_open - t_start
    sigs = engine.stats()["compile_events"] - sig0
    lat = sorted(drv.lateness) or [0.0]
    log(f"setup {setup_s:.3f} s; compiles in the window: {state['compiles_window']} "
        f"(new engine signatures: {sigs}); generator lateness max "
        f"{lat[-1] * 1e3:.3f} ms, p99 {lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3:.3f} ms")

    dev = (devices or jax.devices())[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    if plan.loop == "closed":
        attempted = [r for r in drv.records if r.submitted <= t_close]
        failed = 0
    else:
        attempted = drv.records
        failed = sum(not r.done for r in attempted) + len(plan.requests) - len(attempted)
    peaks = peaks if peaks is not None else load_peaks(dev.device_kind)
    # a closed loop's tail ends with the window; an open loop's waits for
    # the answers due in it
    judged_until = t_close if plan.loop == "closed" else t_end
    run = RunData(spec, seconds, setup_s, t_open, t_close, t_end, judged_until,
                  drv.records, attempted, peaks, state.get("trace_t0"),
                  state.get("trace_t1"))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices) if devices is not None else len(jax.devices()),
              "memory_peak_bytes": int(mem)}
    breakdown = None
    if trace:
        run.events = [e for e in tracer.events()
                      if run.trace_t0 <= e.wall <= run.trace_t1]
        run.trace = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
    metrics_spec = spec.per_layer if trace else spec.end_to_end
    metrics = {}
    for m in metrics_spec:
        value = load_reader(m["name"])(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # ---- correctness: served tokens against the plain reference ----
    sample = sample_for_check(drv.records, plan.sample_tokens, seed)
    key = system.key
    del engine, drv.engine
    system.free()
    gc.collect()
    t_ref = clock()
    correct, checks, judged = check_outputs(spec, key, sample, state.get("kv", []),
                                            failed, others)
    log(f"reference over {len(sample)} requests and {len(state.get('kv', []))} "
        f"caches in {clock() - t_ref:.3f} s")
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if others:
        # each other mode judged in the program's place (never in the
        # benchmark's own runs)
        result["judged"] = judged
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result
