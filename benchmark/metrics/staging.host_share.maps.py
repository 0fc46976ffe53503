"""The reader of ``staging.host_share.merge`` (see that file), for
maps.reconnect."""

import os

from benchmark import load

HERE = os.path.dirname(os.path.abspath(__file__))
read = load(HERE, "staging.host_share.merge").read
