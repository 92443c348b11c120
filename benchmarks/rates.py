"""Rate and percentile arithmetic of the benchmark.

A rate is completed cycles over the span they really took: the window
opens ON a completion, so ``n`` completions inside it are ``n`` whole
cycles, and the span runs to the last of them — never ``n / seconds``,
where the unfinished cycle at the window's edge is worth 1/n of the
reading (8 % at 13 runs). With several closed-loop clients each client
is counted over its own whole cycles."""

from __future__ import annotations

import statistics
from typing import Optional, Sequence


def closed_loop_rate_per_hour(clients: Sequence[Sequence[float]],
                              t_open: float,
                              seconds: float) -> Optional[float]:
    """Cycles per hour of a closed loop: the sum, over its clients, of
    each client's own rate. ``clients[i]`` holds client i's completion
    instants; its completions in ``(t_open, t_open + seconds]`` count,
    over the span from ITS last completion at or before ``t_open`` (its
    own cycle boundary) to the last one counted — whole cycles of that
    client, whatever the phase of the others. With one client this is
    ``n / (last - t_open)``. None when no cycle completed inside the
    window."""
    total, any_cycle = 0.0, False
    for done in clients:
        done = sorted(done)
        before = [t for t in done if t <= t_open]
        inside = [t for t in done if t_open < t <= t_open + seconds]
        if before and inside:
            total += len(inside) / (inside[-1] - before[-1])
            any_cycle = True
    return 3600.0 * total if any_cycle else None


def p50(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def iqr_spread(values: Sequence[float]) -> float:
    """The builder's spread: (Q3 - Q1) / median by
    ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
