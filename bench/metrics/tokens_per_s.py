"""Output tokens served inside the window, over the window's seconds."""


def read(run):
    n = sum(1 for _ in run.tokens_in(run.t_open, run.t_close))
    return n / run.seconds
