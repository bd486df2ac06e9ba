"""Host-to-device bytes (``ops.transfers``) per fact written in the
window, appended or expired."""


def read(ctx):
    ops, written = ctx.get("ops"), ctx.get("facts_written")
    if not ops or not written:
        return None
    return ops["h2d_bytes"] / written
