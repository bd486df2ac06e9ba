"""Fixpoint rounds per materialization (``InferStats.iterations``)."""


def read(ctx):
    infer, units = ctx.get("infer"), ctx.get("units")
    if not infer or not units:
        return None
    return infer["iterations"] / units
