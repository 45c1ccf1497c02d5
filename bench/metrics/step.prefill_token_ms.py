"""Device time of chunked prefill per prompt token: the device time of
every ``jit_serve_chunk`` run in the traced window over the prompt tokens
of the engine's ``prefill_chunk`` events in the window (``c1 - c0`` each:
real tokens, not the padded bucket)."""
import re

_CHUNK = re.compile(r"^jit_serve_chunk(_tp\d+)?\(")


def read(run):
    t = run.trace
    if t is None:
        return None
    ns = sum(e.dur for e in t.modules if _CHUNK.match(e.name))
    tokens = sum(e.data["c1"] - e.data["c0"] for e in run.events or ()
                 if e.kind == "prefill_chunk")
    if not ns or not tokens:
        return None
    return ns / 1e6 / tokens
