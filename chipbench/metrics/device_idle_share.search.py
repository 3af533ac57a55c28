"""Share of the search's traced window in which no operation ran on the
chip (1 - union of device operation intervals / window)."""


def read(ctx):
    tr = ctx.get("trace")
    return None if not tr else 100.0 * tr["idle_share"]
