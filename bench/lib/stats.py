"""Exact percentiles over every request of a window."""
from __future__ import annotations

import math
from typing import Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Element ceil(q * n) (1-indexed) of the sorted values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def latencies(window) -> list:
    """Issue-to-completion seconds of every request completed in the
    window, from the host-clock stamps the loop took."""
    return [d["t_done"] - d["t_issue"] for d in window.completed]
