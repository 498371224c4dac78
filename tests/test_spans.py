"""The front door's phase spans in a real profiler trace on the CPU.

Each case records a ``jax.profiler`` trace of one front-door call and
reads it back with the benchmark's trace reader (``chipbench``): every
span of the call's path is there, each phase span nests inside the
call's own span, the four phases of a call never overlap, and a warm
repartition opens one ``repro.solve`` per balance attempt, numbered by
its ``attempt`` argument.
"""
import jax
import numpy as np
import pytest

from chipbench import spans as program_spans
from chipbench import tracefile
from repro.partition import PartitionProblem, partition, repartition

N, K = 1 << 12, 8
COLD = {"repro.partition", "repro.bootstrap", "repro.bootstrap.keys",
        "repro.bootstrap.sort", "repro.stage", "repro.solve", "repro.fetch"}
WARM = {"repro.repartition", "repro.stage", "repro.solve", "repro.fetch",
        "repro.migration"}
#: a warm solve cut so short that its balance fails and is retried
RETRY = {"max_iter": 1, "max_balance_iter": 1}

needs4 = pytest.mark.skipif(len(jax.devices()) < 4,
                            reason="needs 4 (virtual) jax devices")


@pytest.fixture(scope="module")
def problems():
    rng = np.random.default_rng(7)
    pts = rng.random((N, 2))
    cold = PartitionProblem(points=pts, k=K, epsilon=0.03, seed=1)
    hot = np.exp(-np.sum((pts - 0.2) ** 2, axis=1) / 0.01)
    return cold, cold.replace(weights=1.0 + 30.0 * hot)


@pytest.mark.parametrize("path", [
    "partition",
    "repartition",
    pytest.param("partition-devices4", marks=needs4),
    pytest.param("repartition-devices4", marks=needs4),
])
def test_phase_spans(path, problems, tmp_path):
    cold, step = problems
    warm = path.startswith("repartition")
    devices = 4 if path.endswith("devices4") else None
    prev = partition(cold, devices=devices) if warm else None
    with jax.profiler.trace(str(tmp_path)):
        if warm:
            res = repartition(step, prev, devices=devices, **RETRY)
        else:
            res = partition(cold, devices=devices)
    found = program_spans.Spans(
        tracefile.load(tracefile.find_xspace(str(tmp_path))))
    names = {s.name for s in found.spans}
    assert names == (WARM if warm else COLD)

    (outer,) = found.named("repro.repartition" if warm
                           else "repro.partition")
    assert outer.args["n"] == N and outer.args["k"] == K
    assert outer.args["method"] == "geographer"
    assert "call" in outer.args
    for s in found.spans:
        assert outer.start <= s.start <= s.end <= outer.end, s

    phases = sorted((s for s in found.spans
                     if s.name in program_spans.PHASES),
                    key=lambda s: s.start)
    for a, b in zip(phases, phases[1:]):
        assert a.end <= b.start, (a, b)
    if warm:
        solves = found.named("repro.solve")
        retries = res.stats["balance_retries"]
        assert retries >= 1
        assert len(solves) == retries + 1
        assert [int(s.args["attempt"]) for s in solves] == list(
            range(retries + 1))
    else:
        assert len(found.named("repro.solve")) == 1
