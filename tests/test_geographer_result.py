"""The balanced-k-means result contract of the front door's four routes:
a cold ``partition()`` or a warm ``repartition()``, on the flat path or
the sharded path at ``devices=1``. Every route builds its
``PartitionResult`` through one function; this pins what it returns."""
import numpy as np
import pytest

from repro.partition import PartitionProblem, partition, repartition

N, K, D = 1000, 8, 2
BASE = {"levels", "final_imbalance"}
WARM = {"iters", "warm_start", "balance_retries", "migration"}
KEYS = {
    "cold-flat": BASE,
    "cold-devices1": BASE | {"devices", "bootstrap"},
    "warm-flat": BASE | WARM,
    "warm-devices1": BASE | WARM | {"devices"},
}


@pytest.fixture(scope="module")
def problems():
    rng = np.random.default_rng(3)
    cold = PartitionProblem(points=rng.random((N, D)), k=K, epsilon=0.05,
                            seed=5)
    return cold, cold.replace(weights=rng.uniform(0.5, 2.0, N))


@pytest.mark.parametrize("route", list(KEYS))
def test_result_contract(route, problems):
    cold, step = problems
    warm = route.startswith("warm")
    devices = 1 if route.endswith("devices1") else None
    if warm:
        res = repartition(step, partition(cold), devices=devices)
    else:
        res = partition(cold, devices=devices)
    assert res.method == "geographer" and res.k == K
    assert res.labels.dtype == np.int64 and res.labels.shape == (N,)
    assert res.labels.min() >= 0 and res.labels.max() < K
    assert np.shape(res.centers) == (K, D)
    assert np.shape(res.influence) == (K,)
    assert set(res.stats) == KEYS[route]
    assert len(res.stats["levels"]) == 1
    assert isinstance(res.stats["final_imbalance"], float)
    if devices is not None:
        assert res.stats["devices"] == 1
    if route == "cold-devices1":
        assert res.stats["bootstrap"] == "host"
    if warm:
        assert res.stats["warm_start"] is True
        assert isinstance(res.stats["iters"], int)
        assert res.stats["balance_retries"] >= 0
