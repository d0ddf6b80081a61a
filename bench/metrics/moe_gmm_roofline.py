"""The least time the expert layer's grouped products could take on one
chip, their FLOPs over the bf16 peak, as a share of their device time per
step (the ``moe_gmm*`` ops, as ``moe_gmm_ms`` finds them).

Per layer and step each of the three products (``w_in``, ``w_gate``,
``w_out``) is ``2 T k d f`` FLOPs over the ``T k`` routed rows of the
node's ``T`` tokens; the backward pass computes two such products for
each (``dlhs``, ``drhs``) and remat computes the forward again: ``24 T k d
f`` a layer with remat, ``18 T k d f`` without.  Rows that tile padding
adds are not counted."""

import trace_reduce
from weights import dims

UNIT = "%"


def flops_per_step(cfg: dict, traffic: dict) -> float:
    """FLOPs of the ``moe_gmm`` calls of one node's step."""
    m = dims(cfg)
    tokens = int(traffic["seq_len"]) * int(traffic["per_node_batch"])
    passes = 3 + 3 * 2 + (3 if cfg["program"]["remat"] else 0)
    return passes * 2.0 * tokens * m["k"] * m["d"] * m["f"] * m["L"]


def read(rec):
    if "trace" not in rec or not dims(rec["config"])["e"]:
        return None
    names = {o.instr for ops in rec["trace"].ops.values() for o in ops
             if o.instr.startswith("moe_gmm")}
    if not names:
        return None
    secs = trace_reduce.mean(trace_reduce.kernel_seconds(
        rec["trace"], names, rec["window_lo"], rec["window_hi"]))
    if secs <= 0:
        return None
    least = flops_per_step(rec["config"], rec["traffic"]) / rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / (secs / rec["steps"])
