"""The benchmark's own copies: generators, reference arithmetic, peak
table and sweep count. Deterministic in the seed, right shapes, right
numbers on hand-made cases."""
import numpy as np
import pytest

from chipbench import gen, plugins, reference, roofline

refined_points = plugins.find("layouts", "refined", "points")


@pytest.mark.parametrize("dim", [2, 3])
def test_refined_points_deterministic(dim):
    a = refined_points(5000, gen.derive_seed(2**40 + 3, 1), dim)
    b = refined_points(5000, gen.derive_seed(2**40 + 3, 1), dim)
    c = refined_points(5000, gen.derive_seed(2**40 + 4, 1), dim)
    assert a.shape == (5000, dim) and a.dtype == np.float64
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # half on the feature (radius 0.3 around the centre), half uniform
    r = np.linalg.norm(a[:2500] - 0.5, axis=1)
    assert abs(np.mean(r) - 0.3) < 0.01
    assert a[2500:].min() >= 0 and a[2500:].max() <= 1


def test_refined_points_rejects_other_dims():
    with pytest.raises(ValueError):
        refined_points(10, 0, 4)


def test_derive_seed_takes_large_seeds():
    s = gen.derive_seed(2**31 + 17, 5)
    assert s.generate_state(1)[0] == gen.derive_seed(2**31 + 17,
                                                     5).generate_state(1)[0]
    assert gen.derive_seed(2**70, 1).generate_state(1).shape == (1,)


def test_imbalance():
    labels = np.array([0, 0, 0, 1])
    assert reference.imbalance(labels, 2) == pytest.approx(0.5)
    w = np.array([1.0, 1.0, 1.0, 3.0])
    assert reference.imbalance(labels, 2, w) == pytest.approx(0.0)


def test_center_gap():
    pts = np.array([[0.0, 0.0], [2.0, 0.0], [10.0, 0.0], [10.0, 4.0]])
    labels = np.array([0, 0, 1, 1])
    # centroids (1, 0) and (10, 2), RMS radii 1 and 2
    at = np.array([[1.0, 0.0], [10.0, 2.0]])
    assert reference.center_gap(pts, labels, at) == pytest.approx(0.0)
    off = np.array([[1.0, 0.5], [10.0, 3.0]])
    assert reference.center_gap(pts, labels, off) == pytest.approx(0.5)
    w = np.array([1.0, 3.0, 1.0, 1.0])        # centroid (1.5, 0), radius √.75
    assert reference.center_gap(pts, labels, at, w) == pytest.approx(
        0.5 / np.sqrt(0.75))


@pytest.mark.parametrize("labels", [[0, 0, 0, 0], [0, 0, 1, 1]])
def test_center_gap_of_degenerate_blocks_is_infinite(labels):
    pts = np.array([[0.0, 0.0], [2.0, 0.0], [5.0, 5.0], [5.0, 5.0]])
    gap = reference.center_gap(pts, np.array(labels), np.zeros((2, 2)))
    assert gap == float("inf")


def test_out_of_range():
    assert reference.out_of_range(np.array([0, 1, 2]), 3, 3) == 0
    assert reference.out_of_range(np.array([0, 3, -1]), 3, 3) == 2
    assert reference.out_of_range(np.array([0, 1]), 3, 3) == 1


def _voronoi(points, centers, influence):
    d = ((points[:, None] - centers[None]) ** 2).sum(-1) / influence ** 2
    return np.argmin(d, axis=1)


def test_assign_gap():
    rng = np.random.default_rng(0)
    pts = rng.uniform(size=(3000, 3))
    centers = rng.uniform(size=(40, 3))
    infl = rng.uniform(0.8, 1.2, 40)
    labels = _voronoi(pts, centers, infl)
    assert reference.assign_gap(pts, labels, centers, infl) < 1e-12
    bad = labels.copy()
    bad[7] = (bad[7] + 1) % 40
    gap = reference.assign_gap(pts, bad, centers, infl)
    assert gap > 1e-4
    assert reference.assign_gap(pts, bad, centers, infl,
                                rows=np.array([1, 2, 3])) < 1e-12


def test_check_calls_counts_failures():
    rng = np.random.default_rng(1)
    pts = rng.uniform(size=(2000, 2))
    centers = rng.uniform(size=(4, 2))
    infl = np.ones(4)
    labels = _voronoi(pts, centers, infl)
    good = {"points": pts, "weights": None, "labels": labels,
            "centers": centers, "influence": infl}
    worse = dict(good, labels=(labels + 1) % 4)
    limits = {"out_of_range": 0, "imbalance": 10.0, "assign_gap": 1e-6,
              "center_gap": 1e9}
    r = reference.check_calls([good, good], 4, 0, limits)
    assert r["failed_calls"] == 0 and r["assign_gap"] < 1e-12
    r = reference.check_calls([good, worse], 4, 0, limits)
    assert r["failed_calls"] == 1 and r["assign_gap"] > 1e-6


def test_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_sweep_work_and_bound():
    flops, nbytes = roofline.sweep_work(1 << 20, 64, 2)
    assert flops == 2.0 * (1 << 20) * 64 * 2
    assert nbytes == (1 << 20) * 16
    t, bound = roofline.sweep_least_seconds(1 << 20, 64, 2, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    t, bound = roofline.sweep_least_seconds(1 << 20, 1024, 3, "TPU v5 lite")
    assert bound == "compute"
    assert t == pytest.approx(2.0 * (1 << 20) * 1024 * 3 / 197e12)
