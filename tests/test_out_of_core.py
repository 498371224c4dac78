"""Out-of-core sharded deal: chunk streaming, dtype preservation, int32
index capacity, and the warm-path prev-labels sentinel.

The contract under test (partition/distributed.py, DESIGN.md §13):

* ``from_problem(..., chunk=c)`` / ``deal(..., chunk=c)`` /
  ``scatter_labels(..., chunk=c)`` are **bit-identical** to the one-shot
  deal for every chunk size — chunking bounds transient host staging,
  it never changes a result bit.
* The deal preserves the problem's floating dtype: a float32 problem
  never gets a float64 host copy (the memory-gate regression this PR
  fixes — the old deal up-cast everything through ``np.float64``).
* ``cap = ceil(n/P)`` must fit the int32 traced index dtype;
  ``check_index_capacity`` raises a naming error at the front door
  instead of letting indices wrap inside a kernel.
* When a direct warm-path caller omits ``prev_labels``, the dealt
  sentinel is -1 — it can never equal a real assignment, so the no-op
  shortcut cannot fire on a partition that never existed.
"""
import jax
import numpy as np
import pytest

from repro.partition import PartitionProblem, ShardedPartitionProblem
from repro.partition.distributed import (INT32_INDEX_CAP,
                                         check_index_capacity,
                                         partition_sharded,
                                         repartition_sharded)

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 (virtual) jax devices")


def _problem(n=4099, k=8, seed=11, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return PartitionProblem(
        points=rng.random((n, 2)).astype(dtype),
        weights=rng.uniform(0.5, 2.0, n).astype(dtype),
        k=k, epsilon=0.05, seed=seed)


class TestChunkedDealParity:
    """chunked == one-shot, bit for bit, across the awkward cases."""

    @pytest.mark.parametrize("devices", [1, 2, 4, 8])
    @pytest.mark.parametrize("chunk", [1, 13, 100, 1 << 30])
    def test_from_problem_bitexact(self, devices, chunk):
        # n=4099 is prime: chunk never divides n, padding is always live
        prob = _problem()
        one = ShardedPartitionProblem.from_problem(prob, devices)
        spc = ShardedPartitionProblem.from_problem(prob, devices,
                                                   chunk=chunk)
        assert spc.points.dtype == one.points.dtype
        assert np.array_equal(one.points, spc.points)
        assert np.array_equal(one.weights, spc.weights)
        assert np.array_equal(one.gather, spc.gather)
        assert np.array_equal(one.valid, spc.valid)

    def test_chunk_below_cap_and_not_dividing_cap(self):
        # cap = ceil(4099/4) = 1025; chunk=7 is < cap and 7 does not
        # divide 1025 — the last slice is a partial one
        prob = _problem()
        one = prob.to_sharded(4)
        spc = prob.to_sharded(4, chunk=7)
        assert np.array_equal(one.points, spc.points)
        assert np.array_equal(one.gather, spc.gather)

    @pytest.mark.parametrize("chunk", [1, 13, 1 << 30])
    def test_deal_and_scatter_roundtrip(self, chunk):
        prob = _problem()
        sp = prob.to_sharded(4)
        vals = (np.arange(prob.n) * 7 % prob.k).astype(np.int64)
        dealt_one = sp.deal(vals)
        dealt_chunk = sp.deal(vals, chunk=chunk)
        assert np.array_equal(np.asarray(dealt_one),
                              np.asarray(dealt_chunk))
        back = sp.scatter_labels(np.asarray(dealt_chunk), chunk=chunk)
        assert np.array_equal(back, vals)

    @needs8
    def test_chunked_solve_bitexact(self):
        prob = _problem()
        a = partition_sharded(prob, 8)
        b = partition_sharded(prob, 8, chunk=13)
        assert np.array_equal(a.labels, b.labels)
        assert np.array_equal(a.centers, b.centers)


class TestDtypePreservation:
    """A float32 problem must stay float32 through the deal — the old
    path up-cast points AND weights through a full-host float64 copy,
    tripling the deal's peak footprint at d=2."""

    def test_float32_problem_deals_float32(self):
        sp = _problem(dtype=np.float32).to_sharded(4)
        assert sp.points.dtype == np.float32
        assert sp.weights.dtype == np.float32

    def test_float32_chunked_deal_stays_float32(self):
        sp = _problem(dtype=np.float32).to_sharded(4, chunk=17)
        assert sp.points.dtype == np.float32
        assert sp.weights.dtype == np.float32

    def test_float64_problem_keeps_float64(self):
        sp = _problem(dtype=np.float64).to_sharded(4)
        assert sp.points.dtype == np.float64
        assert sp.weights.dtype == np.float64

    def test_unit_weights_follow_points_dtype(self):
        prob = PartitionProblem(points=_problem().points, k=8,
                                epsilon=0.05, seed=11)
        sp = prob.to_sharded(4)
        assert sp.weights.dtype == np.float32

    def test_integer_weights_widen_to_float64(self):
        # non-float weights have no dtype to preserve; they widen safely
        base = _problem()
        prob = PartitionProblem(points=base.points,
                                weights=np.ones(base.n, np.int32),
                                k=8, epsilon=0.05, seed=11)
        assert prob.to_sharded(4).weights.dtype == np.float64

    @needs8
    def test_float32_labels_match_float64_layout(self):
        # dtype preservation changes memory, not the layout: gather and
        # valid are identical for the f32 and f64 views of one problem
        p32, p64 = _problem(dtype=np.float32), _problem(dtype=np.float64)
        s32, s64 = p32.to_sharded(8), p64.to_sharded(8)
        assert np.array_equal(s32.gather, s64.gather)
        assert np.array_equal(s32.valid, s64.valid)


class TestIndexCapacity:
    """cap = ceil(n/P) <= 2**31 - 1 is enforced at the front door."""

    def test_overflow_raises_naming_error(self):
        with pytest.raises(ValueError) as e:
            check_index_capacity(2 ** 31, 1)
        msg = str(e.value)
        assert "int32" in msg and "ceil(n/P)" in msg
        assert str(2 ** 31) in msg          # names n
        assert "more devices" in msg        # names the remedy

    def test_boundary_passes(self):
        assert check_index_capacity(2 ** 31 - 1, 1) == INT32_INDEX_CAP

    def test_more_devices_restore_capacity(self):
        assert check_index_capacity(2 ** 31, 2) == 2 ** 30
        assert check_index_capacity(2 ** 31, (1, 2)) == 2 ** 30

    def test_mesh_tuple_uses_device_product(self):
        with pytest.raises(ValueError):
            check_index_capacity(2 ** 32, (1, 2))
        assert check_index_capacity(2 ** 32, (2, 2)) == 2 ** 30


class TestWarmSentinel:
    """prev_labels=None must never satisfy no-op detection."""

    def test_sentinel_run_still_iterates(self):
        # k=1 with centers0 far off the centroid: if a synthetic
        # "previous partition" could register as unchanged, the solver
        # would no-op at iters=0 and keep the bogus centers. The -1
        # sentinel can't match any real assignment, so it must iterate
        # and pull the center onto the weighted centroid.
        prob = _problem(k=1)
        centers0 = np.array([[10.0, 10.0]])
        res = repartition_sharded(prob, 2, centers0)
        assert int(res.stats["iters"]) >= 1
        assert np.array_equal(res.labels, np.zeros(prob.n, np.int64))
        centroid = np.average(prob.points, axis=0, weights=prob.weights)
        assert np.allclose(np.asarray(res.centers)[0], centroid, atol=1e-3)

    def test_real_prev_labels_still_noop(self):
        # the counterpart: a genuine fixed point re-submitted WITH its
        # labels is recognized and re-emitted at iters=0
        prob = _problem(k=1)
        centroid = np.average(prob.points, axis=0, weights=prob.weights)
        prev = np.zeros(prob.n, np.int64)
        res = repartition_sharded(prob, 2, centroid[None, :],
                                  prev_labels=prev)
        assert int(res.stats["iters"]) == 0
        assert np.array_equal(res.labels, prev)

    def test_sentinel_chunked_matches_oneshot(self):
        prob = _problem(k=4)
        centers0 = prob.points[:4].astype(np.float64)
        a = repartition_sharded(prob, 2, centers0)
        b = repartition_sharded(prob, 2, centers0, chunk=19)
        assert np.array_equal(a.labels, b.labels)
        assert np.array_equal(np.asarray(a.centers), np.asarray(b.centers))
