"""Share of the traced window in which the device sat idle while the
innermost open span was an index build, append or mirror merge
(``hf.index``; ``bench/spans.py``), in percent."""

from bench import spans


def read(ctx):
    return spans.layer_share(ctx, "index and residency")
