"""Share of the window in which a flush leader of the cross-document
batcher waited for co-arriving documents: the union of the program's
``device.batch_window`` spans, in %."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import union_share  # noqa: E402


def read(ctx):
    if not any(n == "device.batch_window" for n, *_ in ctx["spans"]):
        return None
    return union_share(ctx, lambda n: n == "device.batch_window")
