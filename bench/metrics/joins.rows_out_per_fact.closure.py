"""Binding rows the island joins built per fact derived: the
``rows_out`` arguments of the ``hf.join`` spans in the traced window
over the ``rows_fresh`` arguments of the ``hf.write`` spans nested in
an ``hf.infer`` span there (the writes of ``insert_facts`` are not
derived).  The intermediate join results that the island planner's
order and projections decide.  ``None`` on an untraced run, where no
join span carries ``rows_out``, or where nothing was derived."""

from bench import spans, trace


def ratio(ev: dict) -> "float | None":
    """The ratio over the spans that start inside the window of ``ev``
    (``spans.load``'s events)."""
    windows = [(s, e) for s, e, name, _ in ev["spans"]
               if name == trace.WINDOW]
    if not windows:
        return None
    lo, hi = windows[0]
    inside = [sp for sp in ev["spans"] if lo <= sp[0] < hi]
    infers = [(s, e) for s, e, name, _ in inside if name == "hf.infer"]
    rows = [int(args["rows_out"]) for _, _, name, args in inside
            if name == "hf.join" and "rows_out" in args]
    fresh = sum(int(args.get("rows_fresh", 0))
                for s, _, name, args in inside if name == "hf.write"
                and any(a <= s < b for a, b in infers))
    if not rows or fresh == 0:
        return None
    return sum(rows) / fresh


def read(ctx):
    if not ctx.get("trace"):
        return None
    from bench import harness
    path = spans.newest_xplane(harness.ROOT)
    if path is None:
        return None
    return ratio(spans.load(path))
