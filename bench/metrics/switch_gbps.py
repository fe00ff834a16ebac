"""Host-to-device switch rate: the expert bytes of the timed transfers that
landed in the window, over the change of RealEngine.measured_load_time (the
transfer thread's own wall time of fetch plus device_put). Nothing to read
where the window switched nothing."""


def read(run):
    n = len(run.in_window(run.counters.transfers))
    if not n or run.window.measured_load_s <= 0:
        return None
    return n * run.expert_bytes / run.window.measured_load_s / 1e9
