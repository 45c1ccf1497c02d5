"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers need.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per operation run and the ``XLA Modules`` line one per program
run.  Host spans are the ``TraceAnnotation`` events (named ``bench/...``
or ``repro/...``) on the host's threads.  The harness opens one ``bench/window`` span over the
traced window; everything is clipped to it.

* busy time: the union of the operation intervals, averaged over the
  device planes that ran anything; idle share = 1 - busy / window;
* operation and program times, by name; each program run labelled by the
  host span it was enqueued from (its ``run_id`` joins the device's
  program event to the host's ``DoEnqueueProgram`` event);
* idle gaps, each labelled by the innermost host span open at its middle
  (gaps under ``SHORT_GAP_NS`` count as time between operations).
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench/window"
# gaps shorter than this sit between the operations of one program
SHORT_GAP_NS = 2000.0
NO_SPAN = "host: no span"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Ev:
    name: str
    start: float               # ns
    dur: float                 # ns
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


def op_base(e: Ev) -> str:
    """``%ssa_attention.39 = f32[...] custom-call(...)`` -> ``ssa_attention``."""
    head = e.name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def call_operand_dtypes(e: Ev) -> list:
    """Element types of a custom call's operands, from its HLO text."""
    if "custom-call(" not in e.name:
        return []
    args = e.name.split("custom-call(", 1)[1].split("custom_call_target", 1)[0]
    return re.findall(r"(?:^|, )([a-z]+\d*)\[", args)


# operations that only hold other operations (a layer scan's loop)
_CONTAINERS = re.compile(r"^(while|conditional|call)$")


@dataclass
class TraceSummary:
    window: tuple              # (start, end) ns
    ops: list                  # Ev on the first device plane's op line
    modules: list              # Ev on its program line
    host: list                 # Ev host spans with a known prefix
    busy_ns: float             # averaged over device planes
    enqueued: dict = field(default_factory=dict)   # run_id -> host ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def op_seconds(self, keep) -> float:
        """Device seconds of the operations ``keep(ev)`` selects."""
        return sum(e.dur for e in self.ops if keep(e)) / 1e9

    def module_runs(self, label: str) -> list:
        """Program runs enqueued from inside host span ``label`` (a run's
        ``run_id`` names the host ``Execute`` call that launched it)."""
        runs = [e for e in self.modules if e.stats.get("run_id") in self.enqueued]
        at = self.labels_at([self.enqueued[e.stats["run_id"]] for e in runs])
        return [e for e, lab in zip(runs, at) if lab == label]

    def idle_gaps(self) -> list:
        """(start, length) ns of every gap between device operations."""
        gaps, t = [], self.window[0]
        for s, e in _union((ev.start, ev.end) for ev in self.ops):
            if s > t:
                gaps.append((t, s - t))
            t = max(t, e)
        if self.window[1] > t:
            gaps.append((t, self.window[1] - t))
        return gaps

    def labels_at(self, times) -> list:
        """The innermost host span open at each time (spans on one thread
        nest, so a stack swept along the sorted times finds it)."""
        spans = sorted(self.host, key=lambda h: (h.start, -h.dur))
        order = sorted(range(len(times)), key=lambda i: times[i])
        out = [NO_SPAN] * len(times)
        stack, j = [], 0
        for i in order:
            t = times[i]
            while j < len(spans) and spans[j].start <= t:
                while stack and stack[-1].end < spans[j].start:
                    stack.pop()
                stack.append(spans[j])
                j += 1
            while stack and stack[-1].end < t:
                stack.pop()
            if stack:
                out[i] = stack[-1].name
        return out

    def breakdown(self, n: int = 10) -> dict:
        """Top device operations by time, and idle time by host span."""
        by_op = defaultdict(float)
        for e in self.ops:
            base = op_base(e)
            if not _CONTAINERS.match(base):
                by_op[base] += e.dur
        by_label = defaultdict(float)
        gaps = self.idle_gaps()
        long_gaps = [(s, g) for s, g in gaps if g >= SHORT_GAP_NS]
        by_label["device: between operations"] = sum(
            g for _, g in gaps if g < SHORT_GAP_NS)
        for (s, g), label in zip(long_gaps, self.labels_at([s + g / 2 for s, g in long_gaps])):
            by_label[label] += g
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v / 1e9] for k, v in top],
                "idle_gaps": [[k, v / 1e9] for k, v in idle]}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(evs, lo, hi):
    out = []
    for e in evs:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Ev(e.name, s, t - s, e.stats))
    return out


def _events(line, stats_of=(), keep=None):
    """The line's events (those whose name ``keep`` accepts); stats are
    read for the names in ``stats_of`` (``None``: every event)."""
    for e in line.events:
        name = e.name
        if keep is not None and not keep(name):
            continue
        stats = dict(e.stats) if stats_of is None or name in stats_of else {}
        yield Ev(name, float(e.start_ns), float(e.duration_ns), stats)


_EXECUTE = "tpu::System::Execute"
_SEQUENCED = "tpu::System::Execute=>IssueSequencedEvent"


def _enqueue_times(lines) -> dict:
    """run_id -> host time of the ``Execute`` call that launched the run.

    A program run is enqueued (``DoEnqueueProgram``, which carries the
    ``run_id``) either inside the calling thread's ``tpu::System::Execute``
    or on a worker thread inside an ``IssueSequencedEvent`` whose flow id
    (``_c``) names the calling thread's ``Execute`` (``_p``).
    """
    flows, out = {}, {}
    for evs in lines:
        for e in evs:
            if e.name == _EXECUTE and "_p" in e.stats:
                flows[e.stats["_p"]] = e.start
    for evs in lines:
        holders = sorted((h for h in evs if h.name in (_EXECUTE, _SEQUENCED)),
                         key=lambda h: h.start)
        starts = [h.start for h in holders]
        for e in evs:
            if e.name != "DoEnqueueProgram" or "run_id" not in e.stats:
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or holders[i].end < e.start:
                continue
            h = holders[i]
            if h.name == _EXECUTE:
                out[e.stats["run_id"]] = h.start
            elif h.stats.get("_c") in flows:
                out[e.stats["run_id"]] = flows[h.stats["_c"]]
    return out


def reduce_file(path, host_prefixes=("bench/", "repro/")) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    host, devices, host_lines = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            launch = (_EXECUTE, _SEQUENCED, "DoEnqueueProgram")
            for line in plane.lines:
                evs = list(_events(line, launch, keep=lambda n: n in launch
                                   or n.startswith(host_prefixes)))
                host += [e for e in evs if e.name.startswith(host_prefixes)]
                host_lines.append(evs)
        elif _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            ops = list(_events(lines["XLA Ops"])) if "XLA Ops" in lines else []
            mods = (list(_events(lines["XLA Modules"], None))
                    if "XLA Modules" in lines else [])
            devices.append((plane.name, ops, mods))
    spans = [h for h in host if h.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    lo, hi = spans[0].start, spans[0].end
    host = [h for h in host if h.end >= lo and h.start <= hi]
    busy = []
    for _, ops, _ in sorted(devices):
        ops = _clip(ops, lo, hi)
        if ops:
            busy.append(sum(e - s for s, e in _union((o.start, o.end) for o in ops)))
    devices = sorted(devices)
    first_ops = _clip(devices[0][1], lo, hi) if devices else []
    first_mods = _clip(devices[0][2], lo, hi) if devices else []
    return TraceSummary((lo, hi), first_ops, first_mods, host,
                        sum(busy) / len(busy) if busy else 0.0,
                        _enqueue_times(host_lines))


def reduce_dir(directory, host_prefixes=("bench/", "repro/")) -> TraceSummary:
    paths = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(paths[-1], host_prefixes)
