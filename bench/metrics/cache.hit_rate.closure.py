"""Device-array cache hit rate in the window (``ops.cache.stats()``
deltas; an in-place extension of a stale entry counts as a hit, as the
cache's own ``hit_rate`` does), in percent."""


def read(ctx):
    ops = ctx.get("ops")
    if not ops:
        return None
    lookups = ops["cache_hits"] + ops["cache_misses"] + ops["cache_stale"]
    if not lookups:
        return None
    hits = ops["cache_hits"] + min(ops["cache_extended"], ops["cache_stale"])
    return 100.0 * hits / lookups
