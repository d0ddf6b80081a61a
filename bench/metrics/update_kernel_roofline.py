"""The least time DecentLaM's two plane stages could take on one chip
(their bytes over the HBM peak; the stages do a few flops per byte, so
bandwidth bounds them), over the kernel's device time per step."""

import trace_reduce

UNIT = "%"


def read(rec):
    if "trace" not in rec or not rec["kernel_names"]:
        return None
    secs = trace_reduce.mean(trace_reduce.kernel_seconds(
        rec["trace"], rec["kernel_names"], rec["window_lo"], rec["window_hi"]))
    if secs <= 0:
        return None
    least = rec["update_bytes_per_node"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / rec["steps"])
