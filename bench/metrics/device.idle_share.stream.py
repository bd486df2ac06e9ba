"""Share of the traced window in which no operation ran on the device
(``bench/trace.py``), in percent."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["idle_share"] is None:
        return None
    return 100.0 * t["idle_share"]
