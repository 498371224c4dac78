"""Files of the benchmark found by name.

Every piece that belongs to one cell, traffic mix or metric is a file of
its own, ``chipbench/<kind>/<name>.py``: ``loads/<traffic kind>``,
``layouts/<layout>``, ``end_to_end/<metric>`` and ``per_layer/<metric>``.
A later cell or metric adds such files and ``BENCHMARK.json`` entries,
and changes no file that is there.
"""
from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def find(kind: str, name: str, attr: str):
    """``attr`` of ``chipbench/<kind>/<name>.py`` or, where that file is
    absent, of the file named by ``name`` up to its first dot (so
    ``host_ms.cold`` and ``host_ms.steps`` share ``host_ms.py``)."""
    kdir = os.path.join(HERE, kind)
    for stem in dict.fromkeys((name, name.split(".", 1)[0])):
        path = os.path.join(kdir, stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_" + stem.replace(".", "_").replace(
                    "-", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return getattr(mod, attr)
    raise FileNotFoundError(f"no {kind} file for {name!r} under {kdir}")
