"""Seconds from process start to the opening of the window: weights,
profile and compiles, warm placement."""


def read(run):
    return run.setup_s
