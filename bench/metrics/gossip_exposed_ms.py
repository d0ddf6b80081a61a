"""The part of ``gossip_ms`` during which no other op runs on that chip."""

import trace_reduce

UNIT = "ms"


def read(rec):
    if "trace" not in rec:
        return None
    per = trace_reduce.collective(rec["trace"], "collective-permute",
                                  rec["window_lo"], rec["window_hi"])
    if trace_reduce.mean({d: v[0] for d, v in per.items()}) <= 0:
        return None
    return 1e3 * trace_reduce.mean({d: v[1] for d, v in per.items()}) / rec["steps"]
