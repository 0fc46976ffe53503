"""The resolution kernel's share of its roofline, in % (see _roofline)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _roofline import read  # noqa: E402, F401
