"""Share of the window spent in the interpreter's garbage collections of
generation 1 and 2: the union of the program's ``host.gc`` spans, in %.
0.0 where the ``host.gc_collections`` counter counted collections in the
window but none of them left a ``host.gc`` span there."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import union_share  # noqa: E402


def read(ctx):
    S = ctx["seconds"]
    if any(n == "host.gc" and e > 0.0 and s < S
           for n, s, e, _ in ctx["spans"]):
        return union_share(ctx, lambda n: n == "host.gc")
    if any(v > 0 for (n, _), v in ctx["counters"].items()
           if n == "host.gc_collections"):
        return 0.0
    return None
