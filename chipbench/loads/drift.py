"""``repartition()`` at every time step of a 2-D adaptive (AMR) frame
sequence whose point count changes.

The traffic file names the published frames (DIMACS10 ``hugetric``
frames 0, 10 and 20) and the load model assumed between them. Frame 0 is
the configuration's refined layout: its first ``n // 2`` points lie on
the ring, the rest are the uniform bulk. Frame ``t`` keeps every point of
frame ``t - 1`` but the ring points farther than ``coarsen_widths`` ring
widths from the ring moved ``t * shift`` in x (coarsening), in their
order, and then draws new points on the moved ring, with its width,
until it holds ``n_t`` points (refinement). ``n_t`` runs geometrically
through the published sizes, scaled by the configuration's ``n`` over
frame 0's. Each frame carries its identity map to the previous one: for
each of its points, the point's index in frame ``t - 1``, or -1 for a
created point.

Set-up takes a cold ``partition()`` of frame 0, makes one warm step per
padding bucket that the window can reach, on frames of a second sequence
made from the seed, so nothing compiles in the window, and then makes
the window's frames from the seed. Window call ``i`` repartitions frame ``f(i+1)``
from call ``i - 1``'s result (call 0 from the cold result), where ``f``
runs 0, 1, ..., F, F - 1, ..., 0, 1, ...: backwards, the stored frames
are replayed with their maps inverted, so the mesh coarsens.

The reference judges each call at one point count (the first call's).
So every answer is presented to it at the sequence's largest count:
weight-zero copies of the frame's leading points, each with that point's
own label, fill it up. They move neither a block's weight nor its
centroid, and repeat points that are checked anyway, so every reading
is the step's own. An answer with another number of labels than its
frame's points is presented with every label missing.
"""
from __future__ import annotations

import sys

import numpy as np

from chipbench import gen
from chipbench.load import Load as _Load


def bucket(n: int) -> int:
    """The padding bucket of a warm solve over ``n`` points, as the
    front door documents it (docs/api.md): ``n`` rounded up to a
    multiple of ``2 ** (floor(log2 n) - 3)``. Set-up warms one step per
    bucket; a step whose shape it missed shows as a compilation in the
    window."""
    step = 1 << max(int(n).bit_length() - 4, 0)
    return -(-int(n) // step) * step


def sizes(traffic: dict, n0: int) -> list[int]:
    """``n_t`` of frames 0..F: geometric between the published frames,
    scaled by ``n0`` over frame 0's published count."""
    pub = {int(t): int(v) for t, v in traffic["published_n"].items()}
    scale = n0 / pub[0]
    out = []
    for t in range(int(traffic["frames"]) + 1):
        a = max(x for x in pub if x <= t)
        b = min(x for x in pub if x >= t)
        v = pub[a] if a == b else pub[a] * (pub[b] / pub[a]) ** (
            (t - a) / (b - a))
        out.append(int(n0) if t == 0 else int(round(v * scale)))
    return out


def frames(traffic: dict, frame0: np.ndarray, seed):
    """Yields ``(points, identity)`` of frames 0..F, made from ``seed``:
    ``identity`` maps each point to its index in the previous frame, or
    -1 for a created point (None for frame 0)."""
    ring = traffic["ring"]
    c0 = np.asarray(ring["center"], np.float64)
    radius, width = float(ring["radius"]), float(ring["width"])
    reach = float(traffic["coarsen_widths"]) * width
    n_t = sizes(traffic, frame0.shape[0])
    rng = np.random.default_rng(seed)
    pts = frame0
    on_ring = np.arange(frame0.shape[0]) < frame0.shape[0] // 2
    yield pts, None
    for t in range(1, len(n_t)):
        c = c0 + np.array([float(traffic["shift"]) * t, 0.0])
        keep = ~on_ring
        ring_rows = np.flatnonzero(on_ring)
        p = pts[ring_rows]
        dist = np.abs(np.hypot(p[:, 0] - c[0], p[:, 1] - c[1]) - radius)
        keep[ring_rows] = dist <= reach
        kept = np.flatnonzero(keep)
        m = n_t[t] - kept.size
        if m < 0:
            raise ValueError(f"frame {t} keeps {kept.size} points, more "
                             f"than its n_t = {n_t[t]}")
        u = rng.uniform(0.0, 2.0 * np.pi, m)
        rad = radius + rng.normal(0.0, width, m)
        new = np.stack([c[0] + rad * np.cos(u), c[1] + rad * np.sin(u)], 1)
        pts = np.concatenate([pts[kept], new])
        on_ring = np.concatenate([on_ring[kept], np.ones(m, bool)])
        yield pts, np.concatenate([kept, np.full(m, -1, np.int64)])


def inverse(identity: np.ndarray, n_prev: int) -> np.ndarray:
    """The map of the previous frame's points into this frame: each
    point's index here, or -1 for a point this frame coarsened away."""
    inv = np.full(n_prev, -1, np.int64)
    kept = np.flatnonzero(identity >= 0)
    inv[identity[kept]] = kept
    return inv


def frame_index(j: int, last: int) -> int:
    """Frame of step ``j`` of the sequence 0, 1, ..., last, ..., 1, 0,
    1, ..."""
    j %= 2 * last
    return j if j <= last else 2 * last - j


class Load(_Load):
    def setup(self) -> None:
        self.last = int(self.traffic["frames"])
        frame0 = self.points(1, 0)
        cold = self.cold(self.problem(frame0, None, 2, 0))
        # warm one step per bucket the window can reach, each from the
        # cold result, on a second sequence whose maps are composed back
        # to frame 0
        need = {bucket(n) for n in sizes(self.traffic, frame0.shape[0])}
        to0 = np.arange(frame0.shape[0])
        second = frames(self.traffic, frame0,
                        gen.derive_seed(self.seed, 6, 2))
        for t, (pts, identity) in enumerate(second):
            if identity is not None:
                to0 = np.where(identity >= 0, to0[identity], -1)
            if bucket(pts.shape[0]) in need:
                need.discard(bucket(pts.shape[0]))
                self.warm(self.problem(pts, None, 7, t), cold, to0)
            if not need:
                break
        del second, pts, to0
        self.frames, self.maps = map(list, zip(*frames(
            self.traffic, frame0, gen.derive_seed(self.seed, 6, 1))))
        self.back = [None] + [inverse(self.maps[t],
                                      self.frames[t - 1].shape[0])
                              for t in range(1, self.last + 1)]
        self.most = max(p.shape[0] for p in self.frames)
        self.previous = cold

    def warm(self, problem, previous, identity):
        import repro.partition as front
        return front.repartition(problem, previous, method="geographer",
                                 identity=identity, **self.opts)

    def call(self, i: int) -> int:
        a = frame_index(i, self.last)
        b = frame_index(i + 1, self.last)
        identity = self.maps[b] if b > a else self.back[a]
        pts = self.frames[b]
        res = self.warm(self.problem(pts, None, 2, b), self.previous,
                        identity)
        self.previous = res
        self.inputs.append((pts, None))
        self.results.append(res)
        mig = res.stats["migration"]
        print(f"drift: call {i} frame {a}->{b} n {pts.shape[0]} migration "
              f"fraction {mig['fraction']} created {mig['created']} iters "
              f"{res.stats['iters']} retries "
              f"{res.stats['balance_retries']}", file=sys.stderr)
        return pts.shape[0]

    def answer(self, i: int) -> dict:
        """Call ``i``'s answer, presented at the sequence's largest point
        count (module docstring)."""
        out = super().answer(i)
        pts, labels = out["points"], np.asarray(out["labels"])
        n, fill = pts.shape[0], self.most - pts.shape[0]
        weights = np.concatenate([np.ones(n), np.zeros(fill)])
        rows = np.arange(fill) % n
        if labels.shape != (n,):
            labels = np.full(self.most, -1, np.int64)
        else:
            labels = np.concatenate([labels, labels[rows]])
        return {**out, "points": np.concatenate([pts, pts[rows]]),
                "weights": weights, "labels": labels}
