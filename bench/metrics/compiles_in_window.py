"""Executables compiled or loaded from the compile cache while the window's
loop ran (JAX's backend-compile events). It should read 0."""


def read(run):
    return float(len(run.in_window(run.counters.compiles)))
