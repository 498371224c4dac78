"""The program's own phase spans in a profiler trace.

The partitioner's front door opens ``jax.profiler.TraceAnnotation``
spans named ``repro.*`` (``repro.partition`` around a call, and inside
it ``repro.bootstrap``, ``repro.stage``, ``repro.solve`` and
``repro.fetch``; PERF.md lists them all). They land on the host plane
``/host:CPU``, on the clock of the device planes, in either of two
forms: plain (``repro.solve``) or raw, with the span's arguments in the
name (``repro.solve#attempt=1#``).

The trace reduction the harness hands the per-layer readers
(``chipbench.tracefile.Reduction``) keeps the benchmark's call spans and
the host's Python frames, not these. So the readers of the program's
spans read the run's trace file once more: ``for_run`` finds it where
the harness keeps it until its readers are done, in a ``chipbench-*``
directory under the temporary directory, and checks that its call spans
are the run's.
"""
from __future__ import annotations

import glob
import os
import sys
import tempfile
from typing import NamedTuple

import numpy as np

from chipbench import tracefile

PREFIX = "repro."
#: the four spans that between them cover a cold call's host work
PHASES = ("repro.bootstrap", "repro.stage", "repro.solve", "repro.fetch")
#: the benchmark's own span around each call of the window
CALL_SPAN = "chipbench.call"


class Span(NamedTuple):
    start: float
    end: float
    name: str
    args: dict


def base_name(raw: str) -> str:
    """``repro.solve#attempt=1#`` -> ``repro.solve``."""
    return raw.split("#", 1)[0]


def _args(event) -> dict:
    """A span's arguments: the event's stats, else those its raw name
    carries (``name#k=v,k2=v2#``)."""
    args = {str(k): v for k, v in event.stats}
    if not args and "#" in event.name:
        for kv in event.name.split("#")[1].split(","):
            k, _, v = kv.partition("=")
            if k:
                args[k] = v
    return args


class Spans:
    """The ``repro.*`` spans and the benchmark's call spans of one
    trace, each in start order."""

    def __init__(self, xspace):
        found, calls = [], []
        for plane in xspace.planes:
            if plane.name != tracefile.HOST_PLANE:
                continue
            for line in plane.lines:
                for e in line.events:
                    name = base_name(e.name)
                    if name.startswith(PREFIX):
                        found.append(Span(e.start_ns, e.end_ns, name,
                                          _args(e)))
                    elif name == CALL_SPAN:
                        calls.append((e.start_ns, e.end_ns))
        self.spans = sorted(found, key=lambda s: (s.start, -s.end))
        self.calls = sorted(calls)

    def named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def ns(self, name: str, lo: float, hi: float) -> float:
        """Summed ns of the spans named ``name`` inside ``[lo, hi]``."""
        return tracefile.covered(
            tracefile.merge((s.start, s.end) for s in self.named(name)),
            lo, hi)

    def unspanned_ns(self, lo: float, hi: float, busy) -> float:
        """ns of ``[lo, hi]`` that neither a chip op (``busy``: merged
        intervals) nor any of the four phase spans covers."""
        cover = tracefile.merge(
            [tuple(iv) for iv in busy]
            + [(s.start, s.end) for s in self.spans if s.name in PHASES])
        return (hi - lo) - tracefile.covered(cover, lo, hi)

    def innermost(self, lo: float, hi: float) -> str | None:
        """The innermost span that covers at least half of
        ``[lo, hi]``."""
        best = None
        for s in self.spans:
            if min(s.end, hi) - max(s.start, lo) >= 0.5 * (hi - lo) and (
                    best is None or s.end - s.start < best.end - best.start):
                best = s
        return None if best is None else best.name


def _frame(red, S, E, a: float, b: float) -> str:
    """``Reduction.idle_by_host``'s label of the gap ``[a, b]``: the
    innermost Python frame that covers at least half of it, else the one
    that overlaps it most, else ``no host frame``."""
    if len(S):
        ov = np.minimum(E, b) - np.maximum(S, a)
        cand = np.nonzero(ov >= 0.5 * (b - a))[0]
        if len(cand):
            return red.py[cand[np.argmin(E[cand] - S[cand])]][2].lstrip("$")
        if np.max(ov) > 0:
            return red.py[int(np.argmax(ov))][2].lstrip("$")
    return "no host frame"


def idle_by_span(red, spans: Spans, top: int = 10) -> list:
    """Idle seconds of the first chip in the window by the innermost
    ``repro.*`` span that covers at least half of each gap; a gap that
    no span covers so is labelled by its Python frame, as
    ``Reduction.idle_by_host`` labels it."""
    busy = red.busy[min(red.devices)]
    edges = [red.lo]
    for s, e in busy:
        if e <= red.lo or s >= red.hi:
            continue
        edges += [max(s, red.lo), min(e, red.hi)]
    edges.append(red.hi)
    S = np.asarray([p[0] for p in red.py], np.float64)
    E = np.asarray([p[1] for p in red.py], np.float64)
    out = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < tracefile.MIN_GAP_NS:
            continue
        label = spans.innermost(a, b) or _frame(red, S, E, a, b)
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return sorted(out.items(), key=lambda x: -x[1])[:top]


def trace_file() -> str | None:
    """The newest trace under a ``chipbench-*`` directory of the
    temporary directory, where the harness keeps a traced run's trace."""
    files = glob.glob(os.path.join(tempfile.gettempdir(), "chipbench-*",
                                   "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def for_run(run) -> Spans | None:
    """The spans of the traced run ``run``, read once a run; None, with
    the reason on stderr, where its trace file is not found or its call
    spans are not the run's."""
    if not hasattr(run, "program_spans"):
        path = trace_file()
        spans = None if path is None else Spans(tracefile.load(path))
        if spans is None:
            print("spans: no trace file of the run found", file=sys.stderr)
        elif (run.trace.spans_from == "trace"
              and spans.calls != [tuple(s) for s in run.trace.spans]):
            print(f"spans: the call spans of {path} are not the run's",
                  file=sys.stderr)
            spans = None
        run.program_spans = spans
    return run.program_spans


def phase_ms(run, name: str, metric: str) -> float | None:
    """Per-call mean ms of the spans named ``name`` inside each call's
    span; None, with the reason on stderr, where the trace has none."""
    spans = for_run(run) if run.calls else None
    if spans is None or not spans.named(name):
        print(f"{metric}: no {name} span in the trace", file=sys.stderr)
        return None
    per_call = [spans.ns(name, c["start_ns"], c["end_ns"])
                for c in run.calls]
    return float(np.mean(per_call)) * 1e-6
