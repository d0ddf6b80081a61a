"""Mean host time per step spent in ``next()`` on the program's prefetch
iterator, over the traced steps (host clock)."""

UNIT = "ms"


def read(rec):
    waits = rec.get("input_wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
