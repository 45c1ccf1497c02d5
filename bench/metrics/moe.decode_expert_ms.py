"""Device time of the grouped expert matmul (``moe_gmm`` custom calls) in
one decode tick: summed over the calls that ran inside the program runs
launched from the engine's ``repro/decode_dispatch`` span, over the number
of those runs."""
import bisect

from bench import trace_reduce

KERNEL = "moe_gmm"


def read(run):
    t = run.trace
    if t is None:
        return None
    runs = t.module_runs("repro/decode_dispatch")
    calls = sorted((e.start, e.dur) for e in t.ops if trace_reduce.op_base(e) == KERNEL)
    if not runs or not calls:
        return None
    starts = [s for s, _ in calls]
    total = 0.0
    for r in runs:
        lo = bisect.bisect_left(starts, r.start)
        hi = bisect.bisect_right(starts, r.end)
        total += sum(d for _, d in calls[lo:hi])
    return total / len(runs) / 1e6
