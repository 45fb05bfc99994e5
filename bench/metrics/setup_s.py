"""setup_s: process start to the window's start (loading, weights,
compiling or loading every program, warm-up)."""


def read(run):
    return run.setup_s
