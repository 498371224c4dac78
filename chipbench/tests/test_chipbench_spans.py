"""The program's phase spans (``chipbench.spans``) and the readers of
``bootstrap_ms``, ``stage_ms``, ``fetch_ms`` and ``unspanned_ms`` on a
small trace recorded on one TPU v5e by ``chipbench/record_spans.py``: a
cold ``partition()`` of 2^16 + 42 points at k = 64 (span
``chipbench.call``) and one ``repartition()`` step (``chipbench.step``).
On ``tiny_v5e.xplane.pb.gz``, recorded before the program had spans,
the readers leave their metrics out and the accepted readings stand."""
import gzip
import os
import shutil
import tempfile
import types

import pytest

from chipbench import harness, tracefile
from chipbench import spans as program_spans

DATA = os.path.join(harness.ROOT, "chipbench", "testdata")
SPANS = os.path.join(DATA, "spans_v5e.xplane.pb.gz")
TINY = os.path.join(DATA, "tiny_v5e.xplane.pb.gz")
READERS = ("bootstrap_ms", "stage_ms", "fetch_ms", "unspanned_ms")
COLD = {"repro.partition", "repro.bootstrap", "repro.bootstrap.keys",
        "repro.bootstrap.sort", "repro.stage", "repro.solve", "repro.fetch"}
WARM = {"repro.repartition", "repro.stage", "repro.solve", "repro.fetch",
        "repro.migration"}


def _run(red):
    """What a per-layer reader sees of a traced run."""
    return types.SimpleNamespace(
        trace=red, calls=[{"start_ns": s, "end_ns": e} for s, e in red.spans])


def _read(name, run):
    return harness.metric_reader("per_layer", f"{name}.cold")(run)


@pytest.fixture(scope="module")
def xspace():
    return tracefile.load(SPANS)


@pytest.fixture(scope="module")
def red(xspace):
    return tracefile.Reduction(xspace, "chipbench.call")


@pytest.fixture(scope="module")
def spans(xspace):
    return program_spans.Spans(xspace)


@pytest.fixture
def kept_trace(tmp_path, monkeypatch):
    """Lays a recorded trace where the harness keeps a traced run's
    trace until its readers are done, under a temporary directory of
    the test's own."""
    def keep(path):
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
        dest = tmp_path / "chipbench-run" / "plugins" / "profile" / "1"
        dest.mkdir(parents=True)
        with gzip.open(path, "rb") as f, open(dest / "t.xplane.pb",
                                              "wb") as g:
            shutil.copyfileobj(f, g)
    return keep


def test_base_name_and_raw_args():
    assert program_spans.base_name("repro.solve#attempt=1#") == \
        "repro.solve"
    assert program_spans.base_name("repro.stage") == "repro.stage"
    raw = types.SimpleNamespace(name="repro.partition#n=5,k=2#", stats=[])
    assert program_spans._args(raw) == {"n": "5", "k": "2"}


def test_spans_of_each_call(spans, red, xspace):
    """Every span of the cold path inside the cold call, every span of
    the warm path inside the step, each inside its front-door span, and
    the four phases one after another."""
    step = tracefile.Reduction(xspace, "chipbench.step").spans[0]
    for (lo, hi), want, outer in ((red.spans[0], COLD, "repro.partition"),
                                  (step, WARM, "repro.repartition")):
        inside = [s for s in spans.spans if lo <= s.start and s.end <= hi]
        assert {s.name for s in inside} == want
        (door,) = [s for s in inside if s.name == outer]
        assert all(door.start <= s.start and s.end <= door.end
                   for s in inside)
        phases = [s for s in inside if s.name in program_spans.PHASES]
        assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))
    assert spans.calls == red.spans


def test_phase_readers(red, spans, kept_trace):
    """Each reader gives its span's time in the call; with the host time
    inside the solve they add up to ``host_ms``, and what the spans
    leave unnamed is under 1% of it."""
    kept_trace(SPANS)
    run = _run(red)
    s, e = red.spans[0]
    got = {name: _read(name, run) for name in READERS}
    for name in ("bootstrap", "stage", "fetch"):
        ms = got[f"{name}_ms"]
        assert ms == pytest.approx(spans.ns(f"repro.{name}", s, e) * 1e-6)
        assert ms > 0
    host = _read("host_ms", run)
    (solve,) = [v for v in spans.named("repro.solve") if s <= v.start < e]
    solve_host = ((solve.end - solve.start)
                  - red.busy_ns(solve.start, solve.end)) * 1e-6
    parts = (got["bootstrap_ms"] + got["stage_ms"] + got["fetch_ms"]
             + got["unspanned_ms"] + solve_host)
    assert parts == pytest.approx(host, rel=0.01)
    assert 0 <= got["unspanned_ms"] < 0.01 * host


def test_idle_gaps_by_span(red, spans):
    """The chip's idle time in the cold call falls under the program's
    spans, the bootstrap's keys first."""
    gaps = program_spans.idle_by_span(red, spans)
    idle = red.window_s - red.busy_seconds()
    assert gaps[0][0] == "repro.bootstrap.keys"
    named = sum(t for label, t in gaps if label.startswith("repro."))
    assert named >= 0.95 * idle
    assert sum(t for _, t in gaps) <= idle * 1.0001


def test_a_trace_without_spans(kept_trace):
    """On a trace recorded before the program had spans the readers
    leave their metrics out, and the span labels of the idle gaps fall
    back to the host frames, as the accepted breakdown has them."""
    kept_trace(TINY)
    red = tracefile.Reduction(tracefile.load(TINY), "chipbench.call")
    for name in READERS:
        assert _read(name, _run(red)) is None
    none = program_spans.Spans(tracefile.load(TINY))
    assert none.spans == []
    assert program_spans.idle_by_span(red, none) == red.idle_by_host()


def test_another_runs_trace_is_not_read(red, kept_trace):
    kept_trace(SPANS)
    other = types.SimpleNamespace(
        spans_from="trace", spans=[(0.0, 1.0)], busy=red.busy,
        devices=red.devices)
    assert program_spans.for_run(_run(other)) is None
    assert _read("stage_ms", _run(other)) is None


def test_no_kept_trace(red, tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert program_spans.trace_file() is None
    assert _read("bootstrap_ms", _run(red)) is None
