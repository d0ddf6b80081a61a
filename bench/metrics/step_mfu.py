"""Model FLOPs of the traced steps (forward and backward, nothing
recomputed; see ``bench/flops.py``) over the traced window, as a share of
the chips' bf16 peak."""

UNIT = "%"


def read(rec):
    if "trace" not in rec:
        return None
    window_s = rec["window_hi"] - rec["window_lo"]
    work = rec["steps"] * rec["tokens_per_step"] * rec["flops_per_token"]
    return 100.0 * work / (window_s * rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
