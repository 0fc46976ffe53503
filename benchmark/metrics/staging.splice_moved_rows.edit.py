"""Resident rows the OpLog splice rewrote at a new index, over the
row-carrying splices of the window (``oplog.splice_rows_moved`` over
``oplog.splices`` of every ``mode``), in rows a splice. A program that
counts no splice (one before the suffix splice) gives None."""


def read(ctx):
    splices = moved = None
    for (name, _labels), v in ctx["counters"].items():
        if name == "oplog.splices":
            splices = (splices or 0) + v
        elif name == "oplog.splice_rows_moved":
            moved = (moved or 0) + v
    if not splices:
        return None
    return (moved or 0) / splices
