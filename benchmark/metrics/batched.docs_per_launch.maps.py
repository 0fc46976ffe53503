"""Documents a shared launch carried in the window: stages packed
(``device.batch_stages``) over ``device.kernel_launches{path=batched}``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _batched import counts  # noqa: E402


def read(ctx):
    got = counts(ctx)
    if got is None:
        return None
    stages, launches = got
    n = launches.get("batched", 0)
    return stages / n if n else None
