"""Share of the traced window the device spends in the engine's
chunked-prefill programs: every run of ``jit_serve_chunk`` (the program
the engine names after its ``chunk`` jit key) clipped to the window, over
the window."""
import re

_CHUNK = re.compile(r"^jit_serve_chunk(_tp\d+)?\(")


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    runs = [e for e in t.modules if _CHUNK.match(e.name)]
    if not runs:
        return None
    return 100.0 * sum(e.dur for e in runs) / 1e9 / t.window_s
