"""Share of the window's document resolutions that rode a shared launch,
in %: stages packed into ``path=batched`` launches
(``device.batch_stages``) over those plus the per-document launches
(``device.kernel_launches{path=per_doc}``)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _batched import counts  # noqa: E402


def read(ctx):
    got = counts(ctx)
    if got is None:
        return None
    stages, launches = got
    total = stages + launches.get("per_doc", 0)
    return 100.0 * stages / total if total else None
