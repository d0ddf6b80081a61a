"""Device time per step of the expert layer's grouped products: the ops
whose instruction name starts with ``moe_gmm`` (the kernels
``moe_gmm_fwd``, ``moe_gmm_dlhs`` and ``moe_gmm_drhs``), found in the
trace itself; mean over the chips.  None where the program has no such
kernel."""

import trace_reduce

UNIT = "ms"


def read(rec):
    if "trace" not in rec:
        return None
    names = {o.instr for ops in rec["trace"].ops.values() for o in ops
             if o.instr.startswith("moe_gmm")}
    if not names:
        return None
    secs = trace_reduce.mean(trace_reduce.kernel_seconds(
        rec["trace"], names, rec["window_lo"], rec["window_hi"]))
    return 1e3 * secs / rec["steps"] if secs > 0 else None
