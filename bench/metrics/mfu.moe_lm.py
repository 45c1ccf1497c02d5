"""Model operations of an expert LM done in the traced window over the
window times the chip's bf16 peak: each prompt and output token through
the active weights (attention, router, its top-k experts) at 2 operations
per weight, the useful SSA counts over the keys each query may see (at
most the window on window layers), and the head once per read logit row
(``families.moe_lm.token_ops``; the calls from ``work.ssa_calls``)."""
from bench import work
from bench.families import moe_lm


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    ops = 0.0
    for q_pos, keys, logits in work.ssa_calls(run):
        for j, p in enumerate(q_pos):
            ops += moe_lm.token_ops(run.config, p, keys,
                                    logits and j == len(q_pos) - 1)
    if not ops:
        return None
    return 100.0 * ops / (t.window_s * run.peaks["bf16_flops"])
