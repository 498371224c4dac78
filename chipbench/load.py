"""What every load shares: the problem sizes and the front-door options
that the configuration states, and what a call returned.

A traffic file's ``kind`` names ``chipbench/loads/<kind>.py``, whose
``Load`` class derives from the one here and adds ``setup()`` (the work
before the window, which warms every shape the window uses) and
``call(i)`` (window call ``i``: it records its inputs and its answer
and returns the points it partitioned).

Everything goes through the partitioner's front door
(``repro.partition.partition`` and ``repro.partition.repartition``),
looked up on the module at each call. A call ends when its labels are a
host numpy array in the original point order, which the front door
returns.
"""
from __future__ import annotations

import numpy as np

from . import gen


class Load:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 opts: dict | None = None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.n, self.d, self.k = config["n"], config["d"], config["k"]
        self.opts = {"assign_precision": config["precision"], **(opts or {})}
        self.inputs: list = []
        self.results: list = []

    def points(self, *tags: int) -> np.ndarray:
        return gen.points(self.config, self.seed, *tags)

    def problem(self, points, weights, *tags: int):
        from repro.partition import PartitionProblem
        seed = int(gen.derive_seed(self.seed, *tags).generate_state(1)[0])
        return PartitionProblem(points=points, k=self.k, weights=weights,
                                epsilon=self.config["epsilon"], seed=seed)

    def cold(self, problem):
        import repro.partition as front
        return front.partition(problem, method="geographer", **self.opts)

    def answer(self, i: int) -> dict:
        """Call ``i``'s inputs, as the benchmark made them, and its
        answer."""
        res = self.results[i]
        points, weights = self.inputs[i]
        return {"points": points, "weights": weights,
                "labels": res.labels, "centers": res.centers,
                "influence": res.influence}

    def sweeps(self, i: int) -> int:
        """Assign sweeps of call ``i`` from the stats it returned: the
        balance iterations of every movement iteration (warm-up rounds
        included), the final balance pass, and a warm start's pre-pass."""
        res = self.results[i]
        lvl = res.stats["levels"][0]
        it = int(lvl["iters"])
        hist = np.asarray(lvl["history"]["balance_iters"])[:it]
        pre = 1 if res.stats.get("warm_start") else 0
        return int(np.sum(hist)) + int(lvl["final_balance_iters"]) + pre
