"""The harness at tiny sizes on the CPU: BENCHMARK.json and the files it
names, the refusal without a chip, sound runs, the bf16 control, and the
faults a cell can have, each of which ``correct`` must refuse."""
import io
import json
import os
import re

import numpy as np
import pytest

from chipbench import faults, harness, plugins, tracefile

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELL = "cold.refined2d-n5824554-k64"
TINY = {"n": 4096, "k": 16}


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(ROOT, "BENCHMARK.json")


def test_benchmark_names_its_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        cfg = harness.load_json(ROOT, c["file"])
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["precision"] == "f32"
        assert set(cfg["limits"]) == {"assign_gap", "center_gap"}
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and len(cell["why"]) <= 200
        _, _, cfg, traffic = harness.load_cell(ROOT, cell["name"])
        assert cfg["chips"] == cell["chips"]
        assert harness.make_load(cfg, traffic, 1).n == cfg["n"]
        reported = harness.cell_metrics(bench, cell["name"], "end_to_end")
        names = {m["name"] for m in reported}
        assert "setup_s" in names and len(names) >= 2
        layers = harness.cell_metrics(bench, cell["name"], "per_layer")
        assert layers
        for m in layers:
            assert m["moves"] in names
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert callable(harness.metric_reader("per_layer", m["name"]))
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert callable(harness.metric_reader("end_to_end", m["name"]))


def _run(workload, seed=2**31 + 99, seconds=0.5, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(workload, seed, seconds, False, require_tpu=False,
                     compile_cache=False,
                     config_override=TINY, out=out, err=err, **kw)
    assert rc == 0, err.getvalue()
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), err.getvalue()


def test_cpu_is_refused(capsys, monkeypatch):
    from chipbench import run
    for var in ("JAX_COMPILATION_CACHE_DIR", "JAX_COMPILATION_CACHE_MAX_SIZE",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.delenv(var, raising=False)   # restored afterwards
    rc = run.main(["--workload", CELL, "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_unknown_workload_is_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell(ROOT, "no.such-cell")


@pytest.mark.parametrize("seed", [2**31 + 99, 7])
def test_sound_run(seed):
    res, err = _run(CELL, seed)
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"out_of_range", "imbalance", "assign_gap",
                                  "center_gap"}
    assert res["checks"]["imbalance"]["limit"] == 0.03
    assert "setup_s" in res["metrics"] and len(res["metrics"]) == 2
    assert res["device"]["count"] == 1
    assert "compilations in the window: {'traces': 0, 'compiles': 0}" in err
    assert err.strip().splitlines()[-1].startswith("check ")


def test_same_seed_same_inputs():
    _, _, cfg, traffic = harness.load_cell(ROOT, CELL)
    cfg = {**cfg, **TINY}
    a = harness.make_load(cfg, traffic, 2**33 + 1).points(1, 0)
    b = harness.make_load(cfg, traffic, 2**33 + 1).points(1, 0)
    c = harness.make_load(cfg, traffic, 2**33 + 2).points(1, 0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_control_is_refused():
    res, err = _run(CELL, opts={"assign_precision": "bf16"})
    assert res["correct"] is False, err
    assert res["checks"]["assign_gap"]["value"] > \
        res["checks"]["assign_gap"]["limit"]


# ---------------------------------------------------------------------------
# faults planted under the front door, and solves cut short


@pytest.fixture
def fresh_jit():
    """No compiled program crosses into or out of a faulted run."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_refused(fault, monkeypatch, fresh_jit):
    faults.plant(fault, monkeypatch.setattr)
    res, err = _run(CELL)
    assert res["correct"] is False, err
    assert res["failed"] >= 1


@pytest.mark.parametrize("max_iter", [1, 2])
def test_cut_solve_is_refused(max_iter):
    """Centers moved too few times sit off their blocks' centroids."""
    res, err = _run(CELL, opts={"max_iter": max_iter})
    assert res["correct"] is False, err
    assert res["checks"]["center_gap"]["value"] > \
        res["checks"]["center_gap"]["limit"]


# ---------------------------------------------------------------------------
# the per-layer readers on the recorded trace


class _FakeRun:
    def __init__(self, red, sweeps, n, k, d, chips=1):
        self.trace = red
        self.device_kind = "TPU v5 lite"
        self.chips = chips
        self.calls = [{"start_ns": s, "end_ns": e, "sweeps": sweeps,
                       "n": n, "k": k, "d": d} for s, e in red.spans]


@pytest.fixture(scope="module")
def traced():
    path = os.path.join(ROOT, "chipbench", "testdata",
                        "tiny_v5e.xplane.pb.gz")
    red = tracefile.Reduction(tracefile.load(path), "chipbench.call")
    return _FakeRun(red, sweeps=190, n=1 << 16, k=64, d=2)


def test_readers_on_recorded_trace(traced, bench):
    red = traced.trace
    s, e = red.spans[0]
    host = harness.metric_reader("per_layer", "host_ms.cold")(traced)
    assert host == pytest.approx((e - s - red.busy_ns(s, e)) * 1e-6)
    assert harness.metric_reader("per_layer", "sweeps.cold")(traced) == 190
    idle = harness.metric_reader("per_layer", "idle_frac.cold")(traced)
    assert 0 < idle < 100
    assert idle == pytest.approx(100 * (1 - red.busy_seconds()
                                        / red.window_s))
    roof = harness.metric_reader("per_layer", "assign_roofline.cold")(traced)
    assert 0 < roof < 100


def test_roofline_reader_is_silent_without_its_kernel(traced, monkeypatch):
    reader = harness.metric_reader("per_layer", "assign_roofline.cold")
    monkeypatch.setitem(reader.__globals__, "KERNEL_NAMES", ("renamed",))
    assert reader(traced) is None


def test_end_to_end_readers():
    window = harness.Window([(0.0, 2.0, 10), (2.0, 5.0, 20)], 7.5)
    assert harness.metric_reader("end_to_end", "points_per_s")(window) == 6.0
    assert harness.metric_reader("end_to_end", "setup_s")(window) == 7.5


@pytest.mark.parametrize("kind, name", [("loads", "no_such_kind"),
                                        ("per_layer", "no_such.metric"),
                                        ("end_to_end", "no_such_metric")])
def test_missing_file_is_refused(kind, name):
    with pytest.raises(FileNotFoundError):
        plugins.find(kind, name, "read")
