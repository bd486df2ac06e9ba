"""Share of the traced window in which the device sat idle while the
innermost open span was the fixpoint driver's (``hf.infer``,
``hf.round``, ``hf.plan``; ``bench/spans.py``), in percent."""

from bench import spans


def read(ctx):
    return spans.layer_share(ctx, "fixpoint driver")
