"""Device time of one run of the jitted decode program, averaged over the
decode ticks of the traced window.  A program run is the decode step when
it was launched from inside the engine's ``repro/decode_dispatch`` span."""


def read(run):
    t = run.trace
    if t is None:
        return None
    runs = t.module_runs("repro/decode_dispatch")
    if not runs:
        return None
    return sum(e.dur for e in runs) / len(runs) / 1e6
