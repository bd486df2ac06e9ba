"""Share of the traced window in which the device sat idle while the
innermost open span was the write side's (``hf.write``;
``bench/spans.py``), in percent."""

from bench import spans


def read(ctx):
    return spans.layer_share(ctx, "write side")
