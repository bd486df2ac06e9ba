"""Share of the traced window in which the device sat idle while the
innermost open span was a host<->device transfer (``hf.d2h``,
``hf.h2d``; ``bench/spans.py``), in percent."""

from bench import spans


def read(ctx):
    return spans.layer_share(ctx, "device backend")
