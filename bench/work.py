"""The served work inside a traced window, for the per-layer readers.

Decode tokens come from the run's own records (the host clock stamps each
token when the step that made it returns; the step waits for the device).
Prefill chunks come from the engine's ``prefill_chunk`` events.  A
one-shot prefill (a model served without chunks) is its request's first
token.
"""
from __future__ import annotations

from . import counts


def ssa_calls(run):
    """(query positions, keys, logits read) of every attention call per
    sequence whose work finished inside the traced window."""
    t0, t1 = run.trace_t0, run.trace_t1
    prompt_len = {r.req.uid: len(r.planned.prompt) for r in run.records}
    calls = []
    chunked = any(e.kind == "prefill_chunk" for e in run.events or ())
    for e in run.events or ():
        if e.kind == "prefill_chunk":
            c0, c1 = e.data["c0"], e.data["c1"]
            calls.append((list(range(c0, c1)), c1, c1 == prompt_len.get(e.uid)))
    for rec, i, _ in run.tokens_in(t0, t1):
        p = len(rec.planned.prompt)
        if i == 0:
            if not chunked:
                calls.append((list(range(p)), p, True))
        else:
            calls.append(([p + i - 1], p + i, True))
    return calls


def model_ops(run) -> float:
    """Model operations of the work done in the traced window: every
    prompt and output token through the stack, attention over the keys
    each may see, the head once for each token whose logits were read."""
    cfg = run.config
    causal = cfg["attention"]["causal"]
    ops = 0.0
    for q_pos, keys, logits in ssa_calls(run):
        for j, p in enumerate(q_pos):
            vis = min(p + 1, keys) if causal else keys
            ops += counts.token_ops(cfg, vis, logits=logits and j == len(q_pos) - 1)
    return ops


def roofline_share(run, kernel_seconds: float) -> tuple:
    """(share %, bound) of the SSA kernel: the least time the chip needs
    for the calls' operations and bytes over the kernel's device time."""
    if not kernel_seconds:
        return None, None
    peak_ops = run.peaks["bf16_flops"]
    bw = run.peaks["hbm_bytes_per_s"]
    t_ops = t_bytes = t_min = 0.0
    for q_pos, keys, _ in ssa_calls(run):
        ops, nbytes = counts.ssa_call(run.config, q_pos, keys)
        t_ops += ops / peak_ops
        t_bytes += nbytes / bw
        t_min += max(ops / peak_ops, nbytes / bw)
    bound = "bytes" if t_bytes >= t_ops else "operations"
    return 100.0 * t_min / kernel_seconds, bound
