"""Facts written per row the island joins considered
(``rows_emitted / rows_considered`` of ``InferStats``), in percent."""


def read(ctx):
    infer = ctx.get("infer")
    if not infer or not infer.get("rows_considered"):
        return None
    return 100.0 * infer["rows_emitted"] / infer["rows_considered"]
