"""The trace reduction on a small trace recorded on one TPU v5e: a cold
``partition()`` (span ``chipbench.call``) and one ``repartition()``
step (span ``chipbench.step``) of 2^16 points at k = 64."""
import os
import subprocess
import sys

import pytest

from chipbench import tracefile

TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "tiny_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def xspace():
    return tracefile.load(TRACE)


@pytest.fixture(scope="module")
def red(xspace):
    return tracefile.Reduction(xspace, "chipbench.call")


def test_module_imports_no_jax():
    code = ("import sys; import chipbench.tracefile; "
            "assert 'jax' not in sys.modules")
    root = os.path.dirname(os.path.dirname(os.path.dirname(TRACE)))
    subprocess.run([sys.executable, "-c", code], cwd=root, check=True)


@pytest.mark.parametrize("text, short", [
    ("%assign_reduce_pallas.12 = (s32[1,65536]) custom-call(...)",
     "assign_reduce_pallas"),
    ("%while.130 = (s32[]) while(...)", "while"),
    ("%all-reduce.3 = f32[64] all-reduce(...)", "all-reduce"),
    ("%compare_reduce_fusion.121 = (pred[65536]) fusion(...)",
     "compare_reduce_fusion"),
    ("copy-done.9", "copy-done"),
])
def test_short_name(text, short):
    assert tracefile.short_name(text) == short


def test_merge_and_covered():
    m = tracefile.merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m.tolist() == [[0, 3], [5, 9]]
    assert tracefile.covered(m, 2, 6) == 2.0
    assert tracefile.covered(m, 10, 20) == 0.0
    assert tracefile.covered(tracefile.merge([]), 0, 1) == 0.0


def test_spans_and_window(red):
    assert len(red.spans) == 1
    s, e = red.spans[0]
    assert (red.lo, red.hi) == (s, e)
    assert red.window_s == pytest.approx((e - s) * 1e-9)
    assert red.chips == 1


def test_busy_is_the_solve_module(red, xspace):
    """Busy in the cold call's span equals the span of the jitted solve
    on the chip's ``XLA Modules`` line, to a few microseconds."""
    s, e = red.spans[0]
    plane = next(p for p in xspace.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    solve = [ev for ev in line.events if ev.name.startswith("jit__run_jit")]
    assert len(solve) == 1
    busy = red.busy_ns(s, e)
    assert busy == pytest.approx(solve[0].duration_ns, abs=20_000)
    assert busy < e - s
    assert red.busy_seconds() == pytest.approx(busy * 1e-9)


def test_kernel_time(red):
    ns, count = red.op_ns(["assign_reduce_pallas"])
    assert count == 192
    assert 0 < ns < red.busy_ns(red.lo, red.hi)
    assert red.op_ns(["no_such_kernel"]) == (0.0, 0)


def test_breakdown(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "assign_reduce_pallas"
    # self times: no op is counted inside its while loop again
    assert sum(t for _, t in b["device_ops"]) <= red.busy_seconds() * 1.0001
    # the host bootstrap holds the chip idle in a cold call
    assert b["idle_gaps"][0][0].startswith("sfc.py")
    idle = red.window_s - red.busy_seconds()
    assert sum(t for _, t in b["idle_gaps"]) <= idle * 1.0001


def test_other_span(xspace):
    step = tracefile.Reduction(xspace, "chipbench.step")
    assert len(step.spans) == 1
    assert step.spans[0][0] >= tracefile.Reduction(
        xspace, "chipbench.call").spans[0][1]
    ns, count = step.op_ns(["assign_reduce_pallas"])
    assert count > 0


def test_no_device_plane_is_refused():
    class Empty:
        planes = []
    with pytest.raises(ValueError):
        tracefile.Reduction(Empty(), "chipbench.call")


def test_spans_from_the_host_clock(xspace, red):
    """A trace that holds no span of the call takes the host clock's call
    times; one that holds it keeps its own."""
    s, e = red.spans[0]
    own = tracefile.Reduction(xspace, "chipbench.call", [(0.0, 1.0)])
    assert (own.spans_from, own.spans) == ("trace", red.spans)
    host = tracefile.Reduction(xspace, "no.such.span", [(s, e)])
    assert (host.spans_from, host.spans) == ("host clock", [(s, e)])
    assert host.busy_seconds() == pytest.approx(red.busy_seconds())
    assert tracefile.Reduction(xspace, "no.such.span").spans == []
