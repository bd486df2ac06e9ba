"""Rule evaluations in the window that fell back to a full
re-evaluation (``InferStats.full_evals``; a delta engine expects 0)."""


def read(ctx):
    infer = ctx.get("infer")
    if not infer:
        return None
    return infer["full_evals"]
