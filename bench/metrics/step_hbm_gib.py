"""The compiled step's memory analysis: arguments + outputs + temporaries -
aliased, per chip."""

UNIT = "GiB"


def read(rec):
    return rec.get("hbm_gib")
