"""The drift cell at tiny sizes on the CPU: sound runs, its frames and
identity maps, faults that ``correct`` must refuse, and its readers on a
trace of the cell recorded on one TPU v5e by
``chipbench/record_drift.py``."""
import gzip
import io
import json
import os
import shutil
import tempfile

import numpy as np
import pytest

from chipbench import gen, harness, tracefile
from chipbench import spans as program_spans
from chipbench.roofline import sweep_least_seconds

ROOT = harness.ROOT
CELL = "drift.refined2d-n5824554-k64"
TINY = {"n": 4096, "k": 16}
DRIFT = harness.plugins.find("loads", "drift", "Load")
frames_of, sizes, inverse, frame_index = (
    harness.plugins.find("loads", "drift", name)
    for name in ("frames", "sizes", "inverse", "frame_index"))
DATA = os.path.join(ROOT, "chipbench", "testdata")
TRACE = os.path.join(DATA, "drift_v5e.xplane.pb.gz")
READERS = ("warm_roofline", "pad_share", "migration_ms", "sweeps",
           "host_ms")


def _run(seed=2**31 + 99, seconds=0.5, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(CELL, seed, seconds, False, require_tpu=False,
                     compile_cache=False, config_override=TINY, out=out,
                     err=err, **kw)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


@pytest.fixture(scope="module")
def tiny():
    _, _, cfg, traffic = harness.load_cell(ROOT, CELL)
    return {**cfg, **TINY}, traffic


def _frames(cfg, traffic, seed):
    frame0 = gen.points(cfg, seed, 1, 0)
    return list(frames_of(traffic, frame0, gen.derive_seed(seed, 6, 1)))


@pytest.mark.parametrize("seed", [2**31 + 99, 7])
def test_sound_run(seed, capsys):
    res, err = _run(seed)
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"points_per_s", "setup_s"}
    assert "compilations in the window: {'traces': 0, 'compiles': 0}" in err
    steps = capsys.readouterr().err
    assert "drift: call 0 frame 0->1 n 4147 migration fraction" in steps
    assert "created" in steps


def test_frame0_is_the_cold_cells_mesh():
    """The sequence's configuration starts from the cold cell's mesh, so a
    kernel or solve change is judged at the same frame 0 on both paths,
    and states the frame sizes its traffic runs through."""
    _, _, cold, _ = harness.load_cell(ROOT, "cold.refined2d-n5824554-k64")
    _, _, cfg, traffic = harness.load_cell(ROOT, CELL)
    assert cfg["name"] != cold["name"] and cfg["source"] != cold["source"]
    for key in ("layout", "n", "d", "k", "epsilon", "precision", "chips",
                "limits"):
        assert cfg[key] == cold[key], key
    pub = {int(t): n for t, n in traffic["published_n"].items()}
    assert pub == {0: cfg["published"]["n"],
                   10: cfg["published"]["n_frame10"],
                   20: cfg["published"]["n_frame20"]}
    assert sizes(traffic, cfg["n"])[::10] == [pub[0], pub[10], pub[20]]


def test_same_seed_same_frames(tiny):
    cfg, traffic = tiny
    a = _frames(cfg, traffic, 2**33 + 1)
    b = _frames(cfg, traffic, 2**33 + 1)
    c = _frames(cfg, traffic, 2**33 + 2)
    assert len(a) == traffic["frames"] + 1
    for (pa, ma), (pb, mb) in zip(a, b):
        assert np.array_equal(pa, pb)
        assert (ma is None and mb is None) or np.array_equal(ma, mb)
    assert not np.array_equal(a[1][0], c[1][0])


def test_frames_follow_the_load_model(tiny):
    """Sizes through the published frames, persisting points unmoved, the
    bulk never coarsened, created points on the moved ring, and maps
    that invert."""
    cfg, traffic = tiny
    frames = _frames(cfg, traffic, 11)
    n_t = sizes(traffic, cfg["n"])
    assert [p.shape[0] for p, _ in frames] == n_t
    full = sizes(traffic, traffic["published_n"]["0"])
    assert [full[t] for t in (0, 10, 20)] == [
        traffic["published_n"][k] for k in ("0", "10", "20")]
    bulk = frames[0][0][cfg["n"] // 2:]
    ring = traffic["ring"]
    for t in range(1, len(frames)):
        (prev, _), (pts, identity) = frames[t - 1], frames[t]
        kept = identity >= 0
        assert np.array_equal(pts[kept], prev[identity[kept]])
        assert len(np.unique(identity[kept])) == kept.sum()
        c = np.asarray(ring["center"]) + [traffic["shift"] * t, 0.0]
        new = pts[~kept]
        assert new.shape[0] > 0
        off = np.abs(np.hypot(*(new - c).T) - ring["radius"])
        assert np.median(off) < 2 * ring["width"]
        inv = inverse(identity, prev.shape[0])
        assert np.array_equal(inv[identity[kept]], np.flatnonzero(kept))
    last = frames[-1][0]
    assert all(np.any(np.all(last == p, axis=1)) for p in bulk[:50])


def test_window_runs_back_and_forth():
    assert [frame_index(j, 3) for j in range(9)] == [0, 1, 2, 3, 2, 1, 0, 1,
                                                     2]


def test_answers_are_the_steps_own(tiny):
    """Padding an answer to the sequence's largest count leaves every
    reading of the reference as the answer's own."""
    from chipbench import reference
    cfg, traffic = tiny
    rng = np.random.default_rng(0)
    load = DRIFT(cfg, traffic, 1)
    load.most = 700
    pts = rng.uniform(0, 1, (500, 2))
    labels = rng.integers(0, 4, 500)
    centers = rng.uniform(0, 1, (4, 2))
    res = type("R", (), {"labels": labels, "centers": centers,
                         "influence": np.ones(4)})
    load.inputs, load.results = [(pts, None)], [res]
    got = load.answer(0)
    assert got["points"].shape == (700, 2) and got["labels"].shape == (700,)
    assert reference.imbalance(got["labels"], 4, got["weights"]) == \
        pytest.approx(reference.imbalance(labels, 4))
    assert reference.center_gap(got["points"], got["labels"], centers,
                                got["weights"]) == pytest.approx(
        reference.center_gap(pts, labels, centers))
    assert reference.assign_gap(got["points"], got["labels"], centers,
                                np.ones(4)) == pytest.approx(
        reference.assign_gap(pts, labels, centers, np.ones(4)))
    res.labels = labels[:-1]
    assert reference.out_of_range(load.answer(0)["labels"], 700, 4) == 700


def _stale(res, previous, identity):
    """The previous frame's labels, as they were, for the new points."""
    res.labels = np.asarray(previous.labels)
    return res


def _carried(res, previous, identity):
    """The previous labels carried through the map; the created points
    all in block 0."""
    prev = np.asarray(previous.labels)
    res.labels = np.where(identity >= 0, prev[np.maximum(identity, 0)], 0)
    return res


@pytest.mark.parametrize("fault", [_stale, _carried],
                         ids=["stale", "carried"])
def test_fault_is_refused(fault, monkeypatch):
    import repro.partition as front
    orig = front.repartition

    def faulty(problem, previous, *a, identity=None, **kw):
        res = orig(problem, previous, *a, identity=identity, **kw)
        return fault(res, previous, np.asarray(identity))
    monkeypatch.setattr(front, "repartition", faulty)
    res, err = _run()
    assert res["correct"] is False, err
    assert res["failed"] >= 1


# ---------------------------------------------------------------------------
# the readers on the recorded trace


@pytest.fixture(scope="module")
def side():
    with open(TRACE + ".json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def red():
    return tracefile.Reduction(tracefile.load(TRACE), harness.SPAN)


@pytest.fixture
def run(red, side, tmp_path, monkeypatch):
    """What a per-layer reader sees of the recorded run, with its trace
    kept where the harness keeps a traced run's trace."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    dest = tmp_path / "chipbench-run" / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    with gzip.open(TRACE, "rb") as f, open(dest / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    cls = type("Run", (), {})
    r = cls()
    r.trace, r.device_kind, r.chips = red, "TPU v5 lite", 1
    r.calls = [{"start_ns": s, "end_ns": e, "sweeps": w, "n": side["n"][0],
                "k": side["k"], "d": side["d"]}
               for (s, e), w in zip(red.spans, side["sweeps"])]
    return r


def _read(name, run):
    return harness.metric_reader("per_layer", f"{name}.drift")(run)


def test_recorded_trace_is_the_cell(red, side):
    spans = program_spans.Spans(tracefile.load(TRACE))
    assert len(red.spans) == len(side["n"]) == len(side["sweeps"])
    for (s, e), n in zip(red.spans, side["n"]):
        (door,) = [v for v in spans.named("repro.repartition")
                   if s <= v.start and v.end <= e]
        assert int(door.args["n"]) == n
        solves = [v for v in spans.named("repro.solve")
                  if s <= v.start and v.end <= e]
        assert solves and all(int(v.args["slots"]) >= n for v in solves)


def test_readers_on_recorded_trace(run, red, side):
    spans = program_spans.Spans(tracefile.load(TRACE))
    got = {name: _read(name, run) for name in READERS}
    least = sum(w * sweep_least_seconds(n, side["k"], side["d"],
                                        "TPU v5 lite")[0]
                for n, w in zip(side["n"], side["sweeps"]))
    kernel_ns, _ = red.op_ns(harness.plugins.find(
        "per_layer", "assign_roofline", "KERNEL_NAMES"))
    assert got["warm_roofline"] == pytest.approx(
        100 * least / (kernel_ns * 1e-9))
    assert 0 < got["warm_roofline"] < 100
    slots = [int(next(v for v in spans.named("repro.solve")
                      if s <= v.start).args["slots"]) for s, _ in red.spans]
    assert got["pad_share"] == pytest.approx(
        100 * (sum(slots) - sum(side["n"])) / sum(slots))
    assert 0 < got["pad_share"] < 12.5
    assert got["migration_ms"] == pytest.approx(np.mean(
        [spans.ns("repro.migration", s, e) for s, e in red.spans]) * 1e-6)
    assert got["migration_ms"] > 0
    assert got["sweeps"] == pytest.approx(np.mean(side["sweeps"]))
    assert got["host_ms"] == pytest.approx(np.mean(
        [(e - s) - red.busy_ns(s, e) for s, e in red.spans]) * 1e-6)


def test_readers_are_silent_on_a_cold_trace(tmp_path, monkeypatch):
    """On a trace whose call is a cold ``partition()`` (no
    ``repro.repartition`` span, no ``slots``), as the parent's would be,
    the warm readers leave their metrics out."""
    path = os.path.join(DATA, "spans_v5e.xplane.pb.gz")
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    dest = tmp_path / "chipbench-run" / "plugins" / "profile" / "1"
    dest.mkdir(parents=True)
    with gzip.open(path, "rb") as f, open(dest / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    red = tracefile.Reduction(tracefile.load(path), harness.SPAN)
    cls = type("Run", (), {})
    r = cls()
    r.trace, r.device_kind, r.chips = red, "TPU v5 lite", 1
    r.calls = [{"start_ns": s, "end_ns": e, "sweeps": 10, "n": 1 << 16,
                "k": 64, "d": 2} for s, e in red.spans]
    assert _read("warm_roofline", r) is None
    assert _read("pad_share", r) is None
