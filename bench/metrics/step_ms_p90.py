"""90th percentile of the intervals between consecutive step completions
in the window, the first counted from the window's first dispatch (host
clock)."""

import statistics

UNIT = "ms"


def read(rec):
    done = rec.get("completions")
    if not done or len(done) < 10:
        return None
    times = [rec["t0"]] + list(done)
    gaps = [b - a for a, b in zip(times, times[1:])]
    return 1e3 * statistics.quantiles(gaps, n=10, method="inclusive")[-1]
