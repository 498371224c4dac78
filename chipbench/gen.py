"""Inputs of the benchmark, made from the seed.

A configuration's ``layout`` names ``chipbench/layouts/<layout>.py``,
whose ``points(n, seed, dim)`` makes the points. Layouts are the
benchmark's own copies of the program's generators, so that a change to
the program cannot move the yardstick. Everything is host numpy in
float64; the program receives the arrays as inputs and nothing else.
"""
from __future__ import annotations

import numpy as np

from . import plugins


def derive_seed(seed: int, *tags: int) -> np.random.SeedSequence:
    """A seed sequence for one named input of a run (any whole ``seed``,
    also beyond 32 bits)."""
    return np.random.SeedSequence([int(seed) % (1 << 64), *map(int, tags)])


def points(config: dict, seed: int, *tags: int) -> np.ndarray:
    """[n, d] float64 points of ``config``'s layout for input ``tags``."""
    layout = plugins.find("layouts", config["layout"], "points")
    return layout(config["n"], derive_seed(seed, *tags), config["d"])
