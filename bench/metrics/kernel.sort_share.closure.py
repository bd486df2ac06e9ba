"""Device time in the shared sort programs (``kernels/sortmerge``:
``_xla_sort``, ``_xla_sort_kv``, ``_bitonic``, ``_bitonic_kv``) over
device busy time in the traced window, in percent."""

import re

SORT_PROGRAMS = re.compile(r"_xla_sort|_bitonic")


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["busy_s"] or not t["program_seconds"]:
        return None
    sort_s = sum(v for k, v in t["program_seconds"].items()
                 if SORT_PROGRAMS.search(k))
    return 100.0 * sort_s / t["busy_s"]
