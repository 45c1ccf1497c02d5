"""90th percentile of every gap between consecutive output tokens of a
request, over all requests the window is judged by: the gaps that end
inside the window (an open loop's also while its answers are awaited)."""
from bench.stats import percentile


def read(run):
    gaps = [b - a for r in run.attempted for a, b in zip(r.times, r.times[1:])
            if run.t_open <= b <= run.judged_until]
    return 1e3 * percentile(gaps, 90)
