"""Reduction of a JAX profiler trace to the numbers the per-layer
metrics read.

A trace holds one plane per chip (``/device:TPU:<i>``) whose line
``XLA Ops`` carries every operation that ran, nested (a ``while`` op
contains the ops of its body), and the host plane ``/host:CPU`` whose
line ``python`` carries the benchmark's own ``TraceAnnotation`` spans
and, with the Python tracer on, the host's Python frames. Both planes
are on one clock in nanoseconds.

* busy: the union of a chip's ``XLA Ops`` intervals;
* kernel time: the summed durations of the ops whose short name (the
  HLO name without ``%`` and its ``.<n>`` suffix) is in a list;
* spans: the benchmark's call spans, in call order;
* breakdown: the ops with the most self time, and the idle time of the
  first chip attributed to the innermost host Python frame that covers
  most of each gap.
"""
from __future__ import annotations

import glob
import gzip
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
PY_LINE = "python"
#: idle gaps shorter than this are left out of the attribution
MIN_GAP_NS = 100_000
_SHORT = re.compile(r"^%?([^\s=]+?)(?:\.\d+)*(?:\s*=|$)")


def short_name(hlo_text: str) -> str:
    """``%assign_reduce_pallas.12 = (...)`` -> ``assign_reduce_pallas``."""
    m = _SHORT.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ", 1)[0]


def merge(intervals) -> np.ndarray:
    """[m, 2] sorted, disjoint union of ``(start, end)`` intervals."""
    iv = sorted((float(s), float(e)) for s, e in intervals)
    if not iv:
        return np.zeros((0, 2))
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the merged intervals cover."""
    if not len(merged) or hi <= lo:
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(e - s))


def _self_times(events) -> dict:
    """Self time per short op name over properly nested events."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    self_t = {}
    child = [0.0] * len(evs)
    stack = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    for i, (s, e, name) in enumerate(evs):
        self_t[name] = self_t.get(name, 0.0) + (e - s) - child[i]
    return self_t


class Reduction:
    """Everything the metric readers need from one trace."""

    def __init__(self, xspace, span: str, host_spans=None):
        self.devices = {}          # chip index -> list of (s, e, short)
        py = []
        named = []                 # the span's events on any host line
        for plane in xspace.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    self.devices.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.end_ns, short_name(e.name))
                        for e in line.events)
                elif plane.name == HOST_PLANE:
                    evs = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events]
                    if line.name == PY_LINE:
                        py.extend(evs)
                    named.extend((s, e) for s, e, name in evs
                                 if name == span
                                 or name.startswith(span + "#"))
        if not self.devices:
            raise ValueError("the trace has no /device:TPU:<i> plane with "
                             "an 'XLA Ops' line")
        #: where the call spans came from: the trace's own events, or,
        #: where it holds none, ``host_spans`` (the host clock's call
        #: times on the trace's clock, counted from the trace's start)
        self.spans_from = "trace"
        self.spans = sorted(named)
        if not self.spans and host_spans:
            self.spans_from = "host clock"
            self.spans = sorted((float(s), float(e)) for s, e in host_spans)
        self.py = [(s, e, name) for s, e, name in py
                   if name != span and name.startswith("$")]
        self.busy = {d: merge((s, e) for s, e, _ in evs)
                     for d, evs in self.devices.items()}
        if self.spans:
            self.lo, self.hi = self.spans[0][0], self.spans[-1][1]
        else:
            self.lo = self.hi = 0.0
        self.window_s = (self.hi - self.lo) * 1e-9

    @property
    def chips(self) -> int:
        return len(self.devices)

    def busy_ns(self, lo: float, hi: float, device: int | None = None):
        """Busy ns of ``device`` in ``[lo, hi]``; of any chip when None."""
        if device is not None:
            return covered(self.busy[device], lo, hi)
        return covered(merge(iv for b in self.busy.values() for iv in b),
                       lo, hi)

    def busy_seconds(self) -> float:
        """Busy seconds in the window, averaged over the chips."""
        return float(np.mean([self.busy_ns(self.lo, self.hi, d)
                              for d in self.devices])) * 1e-9

    def op_ns(self, names, lo: float | None = None,
              hi: float | None = None) -> tuple[float, int]:
        """(summed ns, count) over all chips of the ops whose short name
        is in ``names``, inside ``[lo, hi]`` (the window when None)."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        names = set(names)
        tot, cnt = 0.0, 0
        for evs in self.devices.values():
            for s, e, name in evs:
                if name in names and s >= lo and e <= hi:
                    tot += e - s
                    cnt += 1
        return tot, cnt

    def breakdown(self, top: int = 10) -> dict:
        """``device_ops``: the ops with the most self time in the window
        (seconds per chip); ``idle_gaps``: idle seconds of the first chip
        in the window by the host frame that was running."""
        tot = {}
        for evs in self.devices.values():
            inside = [x for x in evs if x[0] >= self.lo and x[1] <= self.hi]
            for name, t in _self_times(inside).items():
                tot[name] = tot.get(name, 0.0) + t
        ops = sorted(((n, t * 1e-9 / self.chips) for n, t in tot.items()),
                     key=lambda x: -x[1])[:top]
        return {"device_ops": [list(x) for x in ops],
                "idle_gaps": [list(x) for x in self.idle_by_host(top)]}

    def idle_by_host(self, top: int = 10) -> list:
        first = min(self.devices)
        busy = self.busy[first]
        edges = [self.lo]
        for s, e in busy:
            if e <= self.lo or s >= self.hi:
                continue
            edges += [max(s, self.lo), min(e, self.hi)]
        edges.append(self.hi)
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2])
                if b - a >= MIN_GAP_NS]
        if not gaps:
            return []
        S = np.asarray([p[0] for p in self.py], np.float64)
        E = np.asarray([p[1] for p in self.py], np.float64)
        names = [p[2].lstrip("$") for p in self.py]
        out = {}
        for a, b in gaps:
            label = "no host frame"
            if len(S):
                ov = np.minimum(E, b) - np.maximum(S, a)
                cand = np.nonzero(ov >= 0.5 * (b - a))[0]
                if len(cand):
                    label = names[cand[np.argmin(E[cand] - S[cand])]]
                elif np.max(ov) > 0:
                    label = names[int(np.argmax(ov))]
            out[label] = out.get(label, 0.0) + (b - a) * 1e-9
        return sorted(out.items(), key=lambda x: -x[1])[:top]


def find_xspace(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str):
    """A ``jax.profiler.ProfileData`` from an ``.xplane.pb`` file, also
    gzipped (``.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce_dir(log_dir: str, span: str, host_spans=None) -> Reduction:
    return Reduction(load(find_xspace(log_dir)), span, host_spans)
