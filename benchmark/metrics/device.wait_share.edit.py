"""Share of the window in which the host was blocked on the device: the
union of the program's ``device.wait`` spans (inside each
``device.readback``, before the copy), in %."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import union_share  # noqa: E402


def read(ctx):
    if not any(n == "device.wait" for n, *_ in ctx["spans"]):
        return None
    return union_share(ctx, lambda n: n == "device.wait")
