"""Dynamic repartitioning: warm-started balanced k-means, migration
metrics, no-op fixed points, cold relabel matching, sharded agreement,
and the acceptance claims on the drifting-hotspot workload."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import meshes, metrics
from repro.core.balanced_kmeans import BKMConfig
from repro.core.timeseries import (simulate_loadbalance,
                                   simulate_loadbalance_scan)
from repro.partition import (PartitionProblem, greedy_center_match,
                             partition, repartition, supports_warm_start,
                             warm_start_methods, weighted_centroids)
from repro.partition.repartition import WARM_DELTA_TOL

EPS = 0.03


def _hotspot_problem(n=3000, k=16, seed=0, t=0,
                     workload=None) -> PartitionProblem:
    rng = np.random.default_rng(seed)
    pts = rng.uniform(0, 1, (n, 2))
    wl = workload or meshes.WORKLOADS["drifting_hotspot"]()
    w = np.asarray(wl.weights_at(pts, t))
    return PartitionProblem(points=pts, k=k, weights=w, epsilon=EPS,
                            seed=seed)


# ---------------------------------------------------------------------------
# migration metrics — hand-computed 6-point cases
# ---------------------------------------------------------------------------

class TestMigrationMetrics:
    PREV = np.array([0, 0, 1, 1, 2, 2])
    NEW = np.array([0, 1, 1, 1, 2, 0])       # points 1 and 5 moved
    W = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])

    def test_weighted_volume(self):
        assert float(metrics.migration_volume(self.PREV, self.NEW,
                                              self.W)) == 8.0   # 2 + 6

    def test_unweighted_volume(self):
        assert float(metrics.migration_volume(self.PREV, self.NEW)) == 2.0

    def test_fraction(self):
        assert float(metrics.migration_fraction(
            self.PREV, self.NEW, self.W)) == pytest.approx(8.0 / 21.0)
        assert float(metrics.migration_fraction(
            self.PREV, self.NEW)) == pytest.approx(2.0 / 6.0)

    def test_retained(self):
        assert float(metrics.retained_fraction(
            self.PREV, self.NEW, self.W)) == pytest.approx(13.0 / 21.0)

    def test_identity_is_zero(self):
        assert float(metrics.migration_volume(self.PREV, self.PREV,
                                              self.W)) == 0.0
        assert float(metrics.retained_fraction(self.PREV,
                                               self.PREV)) == 1.0

    def test_in_graph(self):
        """The same functions trace under jit (sharded-path composition)."""
        import jax
        import jax.numpy as jnp
        frac = jax.jit(metrics.migration_fraction)(
            jnp.asarray(self.PREV), jnp.asarray(self.NEW),
            jnp.asarray(self.W))
        assert float(frac) == pytest.approx(8.0 / 21.0, rel=1e-6)


# ---------------------------------------------------------------------------
# greedy center matching
# ---------------------------------------------------------------------------

class TestGreedyMatch:
    def test_permutation_recovered(self):
        rng = np.random.default_rng(3)
        prev = rng.uniform(0, 1, (8, 2))
        perm = rng.permutation(8)
        mapping = greedy_center_match(prev[perm], prev)
        assert np.array_equal(mapping, perm)
        assert sorted(mapping) == list(range(8))

    def test_noise_tolerant(self):
        rng = np.random.default_rng(4)
        prev = rng.uniform(0, 1, (6, 2)) * 10       # well-separated
        perm = rng.permutation(6)
        new = prev[perm] + rng.normal(0, 0.01, (6, 2))
        assert np.array_equal(greedy_center_match(new, prev), perm)

    def test_shape_mismatch(self):
        with pytest.raises(ValueError):
            greedy_center_match(np.zeros((3, 2)), np.zeros((4, 2)))

    def test_weighted_centroids(self):
        pts = np.array([[0.0, 0.0], [1.0, 0.0], [0.0, 2.0], [0.0, 4.0]])
        lab = np.array([0, 0, 1, 1])
        w = np.array([1.0, 3.0, 1.0, 1.0])
        c = weighted_centroids(pts, lab, 2, w)
        assert c[0] == pytest.approx([0.75, 0.0])
        assert c[1] == pytest.approx([0.0, 3.0])


# ---------------------------------------------------------------------------
# warm start semantics
# ---------------------------------------------------------------------------

class TestWarmStart:
    def test_registry_flags(self):
        assert supports_warm_start("geographer")
        assert supports_warm_start("bkm")           # alias resolves
        assert not supports_warm_start("rcb")
        assert warm_start_methods() == ["geographer"]

    def test_warm_true_rejected_for_rcb(self):
        prob = _hotspot_problem(n=400, k=4)
        prev = partition(prob, method="rcb")
        with pytest.raises(ValueError, match="warm-start"):
            repartition(prob, prev, method="rcb", warm=True)

    def test_k_mismatch_rejected(self):
        prob = _hotspot_problem(n=400, k=4)
        prev = partition(prob, method="geographer")
        with pytest.raises(ValueError, match="k="):
            repartition(prob.replace(k=8), prev)

    def test_n_mismatch_rejected(self):
        prob = _hotspot_problem(n=400, k=4)
        prev = partition(prob, method="geographer")
        smaller = PartitionProblem(points=prob.points[:200], k=4,
                                   epsilon=EPS)
        with pytest.raises(ValueError, match="point set"):
            repartition(smaller, prev)

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10**6))
    def test_unchanged_problem_is_fixed_point(self, seed):
        """Property: repartition with an unchanged problem migrates zero
        weight and needs <= 1 movement iteration."""
        prob = _hotspot_problem(n=1500, k=8, seed=seed % 97)
        prev = partition(prob, method="geographer")
        res = repartition(prob, prev)
        assert res.stats["iters"] <= 1
        assert res.stats["migration"]["volume"] == 0.0
        assert np.array_equal(res.labels, prev.labels)
        assert res.stats["warm_start"] is True

    def test_cold_relabel_reduces_id_churn(self):
        """The greedy matching must keep block ids stable: a cold rcb
        restart of the SAME problem is (near-)identical after matching."""
        prob = _hotspot_problem(n=1000, k=8)
        prev = partition(prob, method="rcb")
        res = repartition(prob, prev, method="rcb")
        assert res.stats["warm_start"] is False
        assert res.stats["relabel_matched"] is True
        # deterministic method + unchanged problem -> same cut, and the
        # matching must recover the identical labeling
        assert np.array_equal(res.labels, prev.labels)
        assert res.stats["migration"]["volume"] == 0.0

    def test_warm_from_centerless_previous_raises(self):
        prob = _hotspot_problem(n=400, k=4)
        prev = partition(prob, method="rcb")        # no centers
        with pytest.raises(ValueError, match="no centers"):
            repartition(prob, prev, method="geographer", warm=True)

    def test_auto_mode_falls_back_cold(self):
        """warm=None + a centerless previous -> cold path, not an error."""
        prob = _hotspot_problem(n=400, k=4)
        prev = partition(prob, method="rcb")
        res = repartition(prob, prev, method="geographer")
        assert res.stats["warm_start"] is False
        assert "migration" in res.stats


# ---------------------------------------------------------------------------
# the acceptance claims: drifting hotspot, T >= 8 steps, k = 16
# ---------------------------------------------------------------------------

class TestAcceptance:
    @pytest.fixture(scope="class")
    def runs(self):
        prob = _hotspot_problem(n=3000, k=16, seed=0)
        wl = meshes.WORKLOADS["drifting_hotspot"]()
        warm = simulate_loadbalance(prob, wl, steps=8, mode="warm")
        cold = simulate_loadbalance(prob, wl, steps=8, mode="cold")
        return warm, cold

    def test_iteration_ratio(self, runs):
        warm, cold = runs
        ratio = (cold["summary"]["mean_iters"]
                 / max(warm["summary"]["mean_iters"], 1e-9))
        assert ratio >= 3.0, (
            f"warm start must use >=3x fewer iterations, got {ratio:.1f}x "
            f"(warm {warm['summary']['mean_iters']}, "
            f"cold {cold['summary']['mean_iters']})")

    def test_migration_ratio(self, runs):
        warm, cold = runs
        ratio = (warm["summary"]["mean_migration_fraction"]
                 / max(cold["summary"]["mean_migration_fraction"], 1e-9))
        assert ratio <= 0.30, (
            f"warm start must move <=30% of cold's weight, got "
            f"{ratio:.3f}")

    def test_balanced_every_step(self, runs):
        warm, cold = runs
        for run in (warm, cold):
            for rec in run["per_step"]:
                assert rec["imbalance"] <= EPS + 1e-6, rec

    def test_migration_accounting_consistent(self, runs):
        warm, _ = runs
        for rec in warm["per_step"]:
            assert rec["retained_fraction"] == pytest.approx(
                1.0 - rec["migration_fraction"])


# ---------------------------------------------------------------------------
# sharded path agreement
# ---------------------------------------------------------------------------

class TestSharded:
    @pytest.fixture(scope="class")
    def setup(self):
        import jax
        if len(jax.devices()) < 4:
            pytest.skip("needs >= 4 (virtual) devices")
        prob0 = _hotspot_problem(n=2000, k=8, seed=1, t=0)
        prob1 = prob0.replace(
            weights=np.asarray(meshes.WORKLOADS["drifting_hotspot"]()
                               .weights_at(prob0.points, 1)))
        prev = partition(prob0, method="geographer")
        return prob0, prob1, prev

    def test_devices1_bit_for_bit(self, setup):
        _, prob1, prev = setup
        single = repartition(prob1, prev)
        d1 = repartition(prob1, prev, devices=1)
        assert np.array_equal(single.labels, d1.labels)
        assert np.array_equal(single.centers, d1.centers)
        assert np.array_equal(single.influence, d1.influence)
        assert single.stats["iters"] == d1.stats["iters"]

    def test_devices4_balance_invariant(self, setup):
        _, prob1, prev = setup
        res = repartition(prob1, prev, devices=4)
        assert res.imbalance() <= EPS + 1e-6
        assert res.stats["warm_start"] is True
        assert len(np.unique(res.labels)) == prob1.k
        # warm advantage survives sharding: far fewer iterations than a
        # cold solve's ~max_iter
        assert res.stats["iters"] <= 10

    def test_devices4_fixed_point(self, setup):
        prob0, _, prev = setup
        res = repartition(prob0, prev, devices=4)
        assert res.stats["migration"]["volume"] == 0.0
        assert res.stats["iters"] == 0


# ---------------------------------------------------------------------------
# scan driver == host loop (permuted space)
# ---------------------------------------------------------------------------

class TestScanDriver:
    def test_scan_matches_host_loop(self):
        prob = _hotspot_problem(n=1500, k=8, seed=2)
        wl = meshes.WORKLOADS["drifting_hotspot"]()
        host = simulate_loadbalance(prob, wl, steps=4, mode="warm")
        prev = partition(
            prob.replace(weights=np.asarray(
                wl.weights_at(prob.points, 0))), method="geographer")
        perm = np.random.default_rng(prob.seed).permutation(prob.n)
        cfg = BKMConfig(k=prob.k, warmup=False, delta_tol=WARM_DELTA_TOL)
        _, recs = simulate_loadbalance_scan(
            prob.points[perm], prev.centers, prev.influence,
            np.asarray(prev.labels)[perm], wl, 4, cfg)
        host_iters = [r["iters"] for r in host["per_step"]]
        assert np.asarray(recs["iters"]).tolist() == host_iters
        np.testing.assert_allclose(
            np.asarray(recs["migration_fraction"]),
            [r["migration_fraction"] for r in host["per_step"]],
            rtol=1e-5, atol=1e-7)

    def test_other_workloads_run(self):
        """Rotating wave + AMR refinement drive the loop balanced too."""
        for name in ("rotating_wave", "amr_refine"):
            prob = _hotspot_problem(n=1200, k=8, seed=3)
            wl = meshes.WORKLOADS[name]()
            sim = simulate_loadbalance(prob, wl, steps=3, mode="warm")
            assert sim["summary"]["all_balanced"], (name, sim["summary"])
            assert sim["workload"] == type(wl).__name__


# ---------------------------------------------------------------------------
# a point set that changes: refinement and coarsening through identity=
# ---------------------------------------------------------------------------

def _refined_step(prob: PartitionProblem, grow: bool, seed: int = 5,
                  weighted: bool = False):
    """The next step of ``prob``'s mesh: a tenth of the points coarsened
    away and, when ``grow``, a fifth as many created in a corner. Returns
    (problem, identity)."""
    rng = np.random.default_rng(seed)
    kept = np.sort(rng.choice(prob.n, size=prob.n - prob.n // 10,
                              replace=False))
    m = prob.n // 5 if grow else 0
    pts = np.concatenate([prob.points[kept], rng.uniform(0, 0.4, (m, 2))])
    identity = np.concatenate([kept, np.full(m, -1)])
    w = rng.uniform(0.5, 2.0, pts.shape[0]) if weighted else None
    return (PartitionProblem(points=pts, k=prob.k, weights=w, epsilon=EPS,
                             seed=seed), identity)


class TestChangingPointSet:
    @pytest.fixture(scope="class")
    def prev(self):
        prob = _hotspot_problem(n=2000, k=8, seed=4)
        return prob, partition(prob, method="geographer")

    @pytest.mark.parametrize("grow", [True, False], ids=["grow", "shrink"])
    def test_step_from_previous_centers(self, prev, grow, monkeypatch):
        import importlib
        rp = importlib.import_module("repro.partition.repartition")
        prob0, res0 = prev
        prob, identity = _refined_step(prob0, grow)
        assert prob.n != prob0.n
        seen = []
        orig = rp.geographer_repartition

        def spy(points, k, centers0, influence0, *a, **kw):
            seen.append((np.array(centers0), np.array(influence0)))
            return orig(points, k, centers0, influence0, *a, **kw)
        monkeypatch.setattr(rp, "geographer_repartition", spy)
        res = repartition(prob, res0, identity=identity)
        assert res.labels.shape == (prob.n,)
        assert res.labels.min() >= 0 and res.labels.max() < prob.k
        assert res.imbalance() <= EPS + 1e-6
        assert res.stats["warm_start"] is True
        assert np.array_equal(seen[0][0], res0.centers)
        assert np.array_equal(seen[0][1], res0.influence)

    @pytest.mark.parametrize("weighted", [False, True],
                             ids=["unit", "weighted"])
    def test_migration_recount(self, prev, weighted):
        prob0, res0 = prev
        prob, identity = _refined_step(prob0, True, weighted=weighted)
        res = repartition(prob, res0, identity=identity)
        w = (np.ones(prob.n) if prob.weights is None
             else np.asarray(prob.weights, np.float64))
        moved = kept_w = created = 0.0
        for i in range(prob.n):                 # the plain recount
            if identity[i] < 0:
                created += w[i]
                continue
            kept_w += w[i]
            if res.labels[i] != res0.labels[identity[i]]:
                moved += w[i]
        mig = res.stats["migration"]
        assert mig["created"] == pytest.approx(created, rel=1e-12)
        assert mig["volume"] == pytest.approx(moved, rel=1e-12)
        assert mig["fraction"] == pytest.approx(moved / kept_w, rel=1e-12)
        assert mig["retained_fraction"] == pytest.approx(1 - moved / kept_w)
        assert 0 < mig["volume"] < kept_w

    def test_same_point_set_creates_nothing(self, prev):
        prob0, res0 = prev
        assert repartition(prob0, res0).stats["migration"]["created"] == 0.0

    def test_one_bucket_compiles_once(self):
        """Five growing steps whose point counts share a padding bucket
        trace and compile the warm solve on the first step only."""
        import jax
        import jax.monitoring

        from repro.core.partitioner import warm_slots
        events = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                  "/jax/core/compile/backend_compile_duration": "compiles"}
        counts = {"traces": 0, "compiles": 0}

        def on(event, duration, **_):
            if event in events:
                counts[events[event]] += 1
        rng = np.random.default_rng(8)
        pts = rng.uniform(0, 1, (2090, 2))
        res = partition(PartitionProblem(points=pts, k=8, epsilon=EPS,
                                         seed=8))
        steps = []
        jax.clear_caches()                 # no solve compiled by another test
        jax.monitoring.register_event_duration_secs_listener(on)
        try:
            for s in range(5):
                new = rng.uniform(0.3, 0.7, (30, 2))
                identity = np.concatenate([np.arange(len(pts)),
                                           np.full(30, -1)])
                pts = np.concatenate([pts, new])
                before = dict(counts)
                res = repartition(PartitionProblem(points=pts, k=8,
                                                   epsilon=EPS, seed=s),
                                  res, identity=identity)
                steps.append({k: counts[k] - before[k] for k in counts})
        finally:
            jax.monitoring.unregister_event_duration_listener(on)
        assert len({warm_slots(2090 + 30 * (s + 1)) for s in range(5)}) == 1
        assert steps[0]["compiles"] >= 1
        assert steps[1:] == [{"traces": 0, "compiles": 0}] * 4

    def test_padded_solve_matches_unpadded(self, prev):
        """Pad slots carry weight zero: the labels are the unpadded
        solve's, the centers agree to float32 summation order (1e-5 of
        the unit square)."""
        from repro.core.partitioner import geographer_repartition, warm_slots
        prob0, res0 = prev
        prob, _ = _refined_step(prob0, True)
        assert warm_slots(prob.n) > prob.n
        cfg = BKMConfig(k=prob.k, epsilon=EPS, warmup=False,
                        delta_tol=WARM_DELTA_TOL)
        runs = [geographer_repartition(
            prob.points, prob.k, res0.centers, res0.influence, cfg=cfg,
            seed=prob.seed, pad=pad) for pad in (False, True)]
        (la, ca, ia, sa), (lb, cb, ib, sb) = runs
        assert np.array_equal(la, lb)
        assert int(sa["iters"]) == int(sb["iters"])
        np.testing.assert_allclose(cb, ca, rtol=0, atol=1e-5)
        np.testing.assert_allclose(ib, ia, rtol=1e-5)
        assert float(sb["skip_fraction_final"]) == pytest.approx(
            float(sa["skip_fraction_final"]), abs=1e-6)

    @pytest.mark.parametrize("grow", [True, False], ids=["grow", "shrink"])
    def test_devices1_bit_for_bit(self, prev, grow):
        prob0, res0 = prev
        prob, identity = _refined_step(prob0, grow)
        single = repartition(prob, res0, identity=identity)
        d1 = repartition(prob, res0, identity=identity, devices=1)
        assert np.array_equal(single.labels, d1.labels)
        assert np.array_equal(single.centers, d1.centers)
        assert np.array_equal(single.influence, d1.influence)
        assert single.stats["iters"] == d1.stats["iters"]
        assert single.stats["migration"] == d1.stats["migration"]

    @pytest.mark.parametrize("bad", ["short", "float", "below", "above"])
    def test_bad_identity_rejected(self, prev, bad):
        prob0, res0 = prev
        prob, identity = _refined_step(prob0, True)
        identity = {"short": identity[:-1],
                    "float": identity.astype(np.float64),
                    "below": np.where(identity < 0, -2, identity),
                    "above": np.where(identity < 0, prob0.n, identity)}[bad]
        with pytest.raises(ValueError, match="identity"):
            repartition(prob, res0, identity=identity)

    def test_cold_relabel_over_persisting_points(self, prev):
        """A centerless method cold-starts and is matched to the previous
        blocks' centroids over the persisting points."""
        prob0, _ = prev
        rcb0 = partition(prob0, method="rcb")
        prob, identity = _refined_step(prob0, True)
        res = repartition(prob, rcb0, method="rcb", identity=identity)
        assert res.stats["relabel_matched"] is True
        assert res.labels.shape == (prob.n,)
        assert res.stats["migration"]["created"] == float(
            np.sum(identity < 0))
