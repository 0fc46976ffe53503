"""The reader of ``host.gc_share.merge`` (see that file), for
maps.reconnect."""

import os

from benchmark import load

HERE = os.path.dirname(os.path.abspath(__file__))
read = load(HERE, "host.gc_share.merge").read
