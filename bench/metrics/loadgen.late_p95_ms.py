"""95th percentile of how late the load generator sent a request:
the moment a worker took it up, minus its due time, in ms."""


def read(ctx):
    late = ctx.get("late_ms")
    if not late:
        return None
    import numpy as np
    return float(np.percentile(late, 95))
