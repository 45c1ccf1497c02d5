"""Set-up time: process start to the window's opening (loading, weights,
warm-up, compiles or compile-cache loads, the closed loop's first round)."""


def read(run):
    return run.setup_s
