"""Device time per step during which a collective-permute (the gossip) is
in flight, mean over the chips."""

import trace_reduce

UNIT = "ms"


def read(rec):
    if "trace" not in rec:
        return None
    per = trace_reduce.collective(rec["trace"], "collective-permute",
                                  rec["window_lo"], rec["window_hi"])
    flight = trace_reduce.mean({d: v[0] for d, v in per.items()})
    return 1e3 * flight / rec["steps"] if flight > 0 else None
