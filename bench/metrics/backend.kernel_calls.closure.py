"""Kernel calls per materialization, Pallas and XLA routes together
(``route_stats()`` deltas)."""


def read(ctx):
    ops, units = ctx.get("ops"), ctx.get("units")
    if not ops or not units:
        return None
    return ops["kernel_calls"] / units
