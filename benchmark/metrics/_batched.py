"""Shared by the cross-document batch readers: the window's counts of
stages packed into shared launches (``device.batch_stages{kind}``) and of
resolution launches by ``path`` (``device.kernel_launches{path}``). A
program that counts no packed stage (one before the full stages joined
the shared launch) gives None."""


def counts(ctx):
    stages = None
    per = {}
    for (name, labels), v in ctx["counters"].items():
        if name == "device.batch_stages":
            stages = (stages or 0) + v
        elif name == "device.kernel_launches":
            path = dict(labels).get("path", "")
            per[path] = per.get(path, 0) + v
    if stages is None:
        return None
    return stages, per
