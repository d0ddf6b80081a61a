"""Process start to the window's first dispatch: imports, weights, the
compile (or the persistent cache's load), the checked first steps."""

UNIT = "s"


def read(rec):
    return rec.get("setup_s")
