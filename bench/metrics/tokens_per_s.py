"""Tokens trained in the window over all nodes, over the time from the
window's first dispatch to its last step's completion (host clock)."""

UNIT = "tokens/s"


def read(rec):
    done = rec.get("completions")
    if not done:
        return None
    return len(done) * rec["tokens_per_step"] / (done[-1] - rec["t0"])
