"""Device-to-host downloads (``hf.d2h`` spans) per traced
materialization (``hf.infer`` spans): each is a point where the host
waits for the device (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    r = spans.reduction(ctx)
    if r is None or not r["infers"]:
        return None
    return r["d2h_calls"] / r["infers"]
