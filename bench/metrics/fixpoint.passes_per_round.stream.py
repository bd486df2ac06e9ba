"""Signed delta passes (+ and -) per streaming round
(``InferStats.delta_passes + neg_passes``)."""


def read(ctx):
    infer, units = ctx.get("infer"), ctx.get("units")
    if not infer or not units:
        return None
    return (infer["delta_passes"] + infer["neg_passes"]) / units
