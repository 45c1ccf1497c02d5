"""Time between consecutive decode programs on the device: the mean of
(start of decode run i+1 - end of decode run i) over every pair of
``jit_serve_decode_seeded`` runs in the traced window with no other
``jit_serve_*`` program between them.  The gap holds the host's work of
one tick: token fetch and commit, scheduling, staging and dispatch (an
admission's chunk programs fall in the gaps this leaves out)."""
import re

_SERVE = re.compile(r"^jit_serve_")
_DECODE = re.compile(r"^jit_serve_decode(_seeded)?(_tp\d+)?\(")


def read(run):
    t = run.trace
    if t is None:
        return None
    runs = sorted((e for e in t.modules if _SERVE.match(e.name)),
                  key=lambda e: e.start)
    gaps = [b.start - a.end for a, b in zip(runs, runs[1:])
            if _DECODE.match(a.name) and _DECODE.match(b.name)]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
