"""Model operations done in the traced window over the window times the
chip's bf16 peak: projections, MLP and head at 2 operations per weight per
token, and the useful SSA score and output counts at unpadded shapes."""
from bench import work


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    ops = work.model_ops(run)
    if not ops:
        return None
    return 100.0 * ops / (t.window_s * run.peaks["bf16_flops"])
