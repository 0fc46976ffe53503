"""The on-chip benchmark of automerge_tpu: ``python3 benchmark/run.py``.

What belongs to one configuration, traffic mix or metric is a file of its
own, found by the name that ``BENCHMARK.json`` or a configuration gives:
``configs/`` and ``traffic/`` hold data; ``kinds/<kind>.py`` is a
deployment kind, ``writers/<writer>.py`` a traffic writer and
``metrics/<metric>.py`` a metric's reader, each loaded by ``load``."""

import importlib.util
import os
import sys


def load(folder: str, name: str):
    """The module ``<folder>/<name>.py``, loaded by its path. It is
    registered as ``benchmark.<folder's last part>.<name>``, so that a
    function it defines can be sent to a spawned worker, which imports the
    same file by that name. A missing file raises FileNotFoundError, which
    names it."""
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path} for the name {name!r}")
    mod_name = f"{__name__}.{os.path.basename(folder)}.{name}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
