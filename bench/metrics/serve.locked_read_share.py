"""Share of reads served under the writer lock: evaluated in full or
folded from the delta windows (``FactServer.stats()["served"]``), over
all reads of the window, in percent."""


def read(ctx):
    served, reads = ctx.get("served"), ctx.get("reads")
    if not served or not reads:
        return None
    return 100.0 * (served.get("full", 0) + served.get("delta", 0)) / reads
