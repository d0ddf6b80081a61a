"""Device time per step of the fused-update kernel's events, found by the
instruction names the compiled step gives them; mean over the chips."""

import trace_reduce

UNIT = "ms"


def read(rec):
    if "trace" not in rec or not rec["kernel_names"]:
        return None
    secs = trace_reduce.mean(trace_reduce.kernel_seconds(
        rec["trace"], rec["kernel_names"], rec["window_lo"], rec["window_hi"]))
    return 1e3 * secs / rec["steps"] if secs > 0 else None
