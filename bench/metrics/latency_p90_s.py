"""90th percentile of the issue-to-completion latency over every request
done in the window, from the loop's host-clock stamps."""
from bench.lib.stats import latencies, nearest_rank


def read(run):
    lat = latencies(run.window)
    return nearest_rank(lat, 0.90) if lat else None
