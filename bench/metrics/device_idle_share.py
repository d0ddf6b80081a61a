"""1 - (union of the device's op intervals / traced window), mean over the
chips.  A device waiting on a collective counts as busy."""

import trace_reduce

UNIT = "%"


def read(rec):
    if "trace" not in rec:
        return None
    lo, hi = rec["window_lo"], rec["window_hi"]
    busy = trace_reduce.busy(rec["trace"], lo, hi)
    if not busy:
        return None
    return 100.0 * (1.0 - trace_reduce.mean(busy) / (hi - lo))
