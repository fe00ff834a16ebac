"""Timed expert loads (RealEngine.load calls) in the window, per 100
requests completed in it."""


def read(run):
    done = len(run.window.completed)
    if not done:
        return None
    return 100.0 * len(run.in_window(run.counters.loads)) / done
