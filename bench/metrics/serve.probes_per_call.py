"""Point probes answered per device call of the probe batcher
(``batched_queries`` over ``device_calls`` in the window)."""


def read(ctx):
    batch = ctx.get("batch")
    if not batch or not batch.get("device_calls"):
        return None
    return batch["batched_queries"] / batch["device_calls"]
