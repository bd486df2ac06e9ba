"""Share of the counting write side's rows looked up by their whole
(id, attr, val) row on the device: the ``match_rows`` arguments of the
``hf.write`` spans in the traced window over ``match_rows`` plus
``match_host`` (rows looked up on the host), in percent.  ``None`` on
an untraced run, or where no write span carries either argument."""

from bench import spans, trace


def share(ev: dict) -> "float | None":
    """The share over the ``hf.write`` spans that start inside the
    window of ``ev`` (``spans.load``'s events)."""
    windows = [(s, e) for s, e, name, _ in ev["spans"]
               if name == trace.WINDOW]
    if not windows:
        return None
    lo, hi = windows[0]
    device = host = 0
    for s, _, name, args in ev["spans"]:
        if name == "hf.write" and lo <= s < hi:
            device += int(args.get("match_rows", 0))
            host += int(args.get("match_host", 0))
    if device + host == 0:
        return None
    return 100.0 * device / (device + host)


def read(ctx):
    if not ctx.get("trace"):
        return None
    from bench import harness
    path = spans.newest_xplane(harness.ROOT)
    if path is None:
        return None
    return share(spans.load(path))
