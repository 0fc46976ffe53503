"""The reader of ``staging.splice_moved_rows.edit`` (see that file), for
fanin.storm."""

import os

from benchmark import load

HERE = os.path.dirname(os.path.abspath(__file__))
read = load(HERE, "staging.splice_moved_rows.edit").read
