"""The reader of ``mirror.drain_ms.merge`` (see that file), for
maps.reconnect."""

import os

from benchmark import load

HERE = os.path.dirname(os.path.abspath(__file__))
read = load(HERE, "mirror.drain_ms.merge").read
