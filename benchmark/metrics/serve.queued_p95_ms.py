"""95th percentile, in ms, of how long a keystroke sat queued: the
durations of the program's ``serve.queued`` spans (one a request, from
its submit to the drain that took it) of ``applyChanges`` requests that
end inside the window. Exact: the nearest-rank order statistic."""

import math


def read(ctx):
    S = ctx["seconds"]
    d = sorted(e - s for name, s, e, f in ctx["spans"]
               if name == "serve.queued" and f.get("method") == "applyChanges"
               and 0.0 <= e <= S)
    if not d:
        return None
    return 1e3 * d[math.ceil(0.95 * len(d)) - 1]
