"""Faults planted under the front door, each of which ``correct`` must
refuse: the CPU tests plant them at a tiny size, and
``chipbench/readings.py --fault`` at a cell's own size on the chip.

``plant(name, setattr_)`` replaces a function of the program through
``setattr_(owner, attribute, value)`` (pytest's ``monkeypatch.setattr``
in the tests, the builtin ``setattr`` in a process of its own). A solve
cut short (``max_iter``) needs no plant: it is a front-door option.
"""
from __future__ import annotations

import numpy as np


def _stale(setattr_) -> None:
    """Every call does its work and returns the first call's answer."""
    from repro.partition import algorithms
    orig, first = algorithms.geographer_partition, []

    def stale(*a, **kw):
        out = orig(*a, **kw)
        if not first:
            first.append(out)
        return first[0]
    setattr_(algorithms, "geographer_partition", stale)


def _half(setattr_) -> None:
    """The solve sees only the first half of the points, in the order
    the caller gave them: the second half carries no weight, so the
    balance is taken over the rest."""
    from repro.partition import algorithms
    orig = algorithms.geographer_partition

    def half(points, k, *a, weights=None, **kw):
        n = points.shape[0]
        w = np.ones(n) if weights is None else np.array(weights, np.float64)
        w[n // 2:] = 0.0
        return orig(points, k, *a, weights=w.astype(points.dtype), **kw)
    setattr_(algorithms, "geographer_partition", half)


def _altered(setattr_) -> None:
    """One label of every answer is changed where it is produced."""
    from repro.partition import algorithms
    orig = algorithms.geographer_partition

    def altered(points, k, *a, **kw):
        out = orig(points, k, *a, **kw)
        labels = np.array(out[0])
        labels[0] = (labels[0] + 1) % k
        return (labels,) + tuple(out[1:])
    setattr_(algorithms, "geographer_partition", altered)


FAULTS = {"stale": _stale, "half": _half, "altered": _altered}


def plant(name: str, setattr_=setattr) -> None:
    FAULTS[name](setattr_)
