"""Roofline share of the grouped expert matmul (``moe_gmm`` custom calls).

Per expert layer of every decode tick and prefill chunk, from the per-layer
stats the engine records on its ``decode_tick`` / ``prefill_chunk`` events
(``moe_pairs``, ``moe_experts``): the larger of ``6 d f`` operations per
routed pair over the bf16 peak and, over the HBM bandwidth, the three
``d x f`` bf16 matrices of each expert with rows (the counter, never all
experts) plus the calls' activations in and out
(``families.moe_lm.expert_call``).  Their sum over the kernel's device time
in the window.

The events hold every program the window dispatched; the device trace may
hold fewer (402 of a window's 611 decode runs in a mellum2 run on one
v5e).  So each kind's counted work is the mean over its events times the
program runs of that kind the trace holds, found by the engine span that
launched them.  A program without the stats, or without the kernel, reads
nothing.
"""
import sys

from bench import trace_reduce
from bench.families import moe_lm

KERNEL = "moe_gmm"
# event kind -> the engine span its program is launched from
SPANS = {"decode_tick": "repro/decode_dispatch",
         "prefill_chunk": "repro/prefill_chunk_dispatch"}


def layer_calls(run, kind):
    """Per event of ``kind`` in the traced window, its expert layers'
    (routed pairs, experts with rows)."""
    for e in run.events or ():
        if e.kind == kind and "moe_pairs" in e.data:
            yield list(zip(e.data["moe_pairs"], e.data["moe_experts"]))


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.op_seconds(lambda e: trace_reduce.op_base(e) == KERNEL)
    if not seconds:
        return None
    peak, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    t_ops = t_bytes = t_min = 0.0
    for kind, span in SPANS.items():
        events = list(layer_calls(run, kind))
        runs = len(run.trace.module_runs(span))
        if not events or not runs:
            continue
        scale = runs / len(events)
        for layers in events:
            for pairs, experts in layers:
                ops, nbytes = moe_lm.expert_call(run.config, pairs, experts)
                t_ops += scale * ops / peak
                t_bytes += scale * nbytes / bw
                t_min += scale * max(ops / peak, nbytes / bw)
    if not t_min:
        return None
    bound = "bytes" if t_bytes >= t_ops else "operations"
    print(f"bench: kernel.moe_experts_roofline bound by {bound}", file=sys.stderr)
    return 100.0 * t_min / seconds
