"""Hilbert SFC properties: locality, bijectivity on grids, np/jnp agreement,
and the table-driven host keys against the per-bit transform."""
import itertools

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sfc import (hilbert_index_np, hilbert_index_jnp,
                            quantize_np, sfc_initial_centers)


def _skilling_transpose(X, bits):
    """Skilling's AxesToTranspose one bit level at a time: inverse undo,
    then Gray encode. X: [n, d] uint64 grid coordinates."""
    X = X.copy()
    n, d = X.shape
    M = np.uint64(1) << np.uint64(bits - 1)
    Q = M
    while Q > np.uint64(1):
        P = Q - np.uint64(1)
        for i in range(d):
            flag = (X[:, i] & Q) != 0
            X[:, 0] = np.where(flag, X[:, 0] ^ P, X[:, 0])  # invert
            t = np.where(~flag, (X[:, 0] ^ X[:, i]) & P, np.uint64(0))
            X[:, 0] ^= t                                    # or exchange
            X[:, i] ^= t
        Q >>= np.uint64(1)
    for i in range(1, d):
        X[:, i] ^= X[:, i - 1]
    t = np.zeros(n, dtype=np.uint64)
    Q = M
    while Q > np.uint64(1):
        flag = (X[:, d - 1] & Q) != 0
        t = np.where(flag, t ^ (Q - np.uint64(1)), t)
        Q >>= np.uint64(1)
    return X ^ t[:, None]


def _oracle_keys(points, bits):
    """Per-bit Hilbert keys: the transposed form's bits interleaved from
    the top level down, axis 0 first."""
    X = _skilling_transpose(quantize_np(np.asarray(points, np.float64), bits),
                            bits)
    key = np.zeros(X.shape[0], dtype=np.uint64)
    for b in range(bits - 1, -1, -1):
        for i in range(X.shape[1]):
            key = (key << np.uint64(1)) | ((X[:, i] >> np.uint64(b)) & np.uint64(1))
    return key


def _points(kind, dim, bits):
    rng = np.random.default_rng(17 * dim + bits)
    if kind == "random":
        return rng.uniform(-3, 5, (5000, dim))
    if kind == "corners":
        # the box's corners fix it to [0, 1]^dim; cell centers of the grid
        # coordinates 0, 1, 2^bits - 2 and 2^bits - 1 on every axis
        g = (np.array([0, 1, 2 ** bits - 2, 2 ** bits - 1]) + 0.5) / 2 ** bits
        cells = np.array(list(itertools.product(g, repeat=dim)))
        corners = np.array(list(itertools.product([0.0, 1.0], repeat=dim)))
        return np.concatenate([corners, cells, rng.uniform(0, 1, (500, dim))])
    if kind == "equal":
        return np.full((100, dim), 0.375)
    return rng.uniform(-1, 1, (1, dim))


@pytest.mark.parametrize("kind", ["random", "corners", "equal", "single"])
@pytest.mark.parametrize("dim,bits", [(2, 1), (2, 4), (2, 5), (2, 6), (2, 7),
                                      (2, 31), (3, 1), (3, 3), (3, 4), (3, 7),
                                      (3, 21)])
def test_table_keys_match_per_bit_transform(dim, bits, kind):
    pts = _points(kind, dim, bits)
    keys = hilbert_index_np(pts, bits=bits)
    assert keys.dtype == np.uint64
    assert np.array_equal(keys, _oracle_keys(pts, bits))


@pytest.mark.parametrize("weighted", [False, True])
def test_initial_centers_match_per_bit_order(weighted):
    rng = np.random.default_rng(5)
    n, k = 1 << 16, 64
    pts = np.concatenate([rng.uniform(0, 1, (n // 2, 2)),
                          rng.normal(0.5, 0.05, (n // 2, 2))])
    w = rng.uniform(0.5, 2.0, n) if weighted else None
    order = np.argsort(_oracle_keys(pts, 31), kind="stable")
    if w is None:
        pos = (np.arange(k) * n) // k + n // (2 * k)
    else:
        cw = np.cumsum(w[order])
        pos = np.searchsorted(cw, (np.arange(k) + 0.5) * (cw[-1] / k))
    expect = pts[order[np.minimum(pos, n - 1)]]
    assert np.array_equal(sfc_initial_centers(pts, k, w), expect)


@pytest.mark.parametrize("dim", [2, 3])
def test_locality(dim):
    rng = np.random.default_rng(0)
    pts = rng.uniform(0, 1, (4000, dim))
    keys = hilbert_index_np(pts)
    order = np.argsort(keys)
    d_sorted = np.linalg.norm(np.diff(pts[order], axis=0), axis=1).mean()
    d_rand = np.linalg.norm(np.diff(pts, axis=0), axis=1).mean()
    assert d_sorted < 0.3 * d_rand


def test_bijective_on_grid_2d():
    """Every cell of a 2^b x 2^b grid gets a distinct key covering 0..4^b-1."""
    b = 4
    g = np.arange(2 ** b)
    xs, ys = np.meshgrid(g, g, indexing="ij")
    pts = np.stack([xs.ravel(), ys.ravel()], 1).astype(np.float64)
    pts = pts / (2 ** b - 1) * (1 - 2 ** -b) + 2 ** -(b + 1)  # cell centers
    keys = hilbert_index_np(pts, bits=b)
    assert len(np.unique(keys)) == 4 ** b
    assert keys.min() == 0 and keys.max() == 4 ** b - 1


def test_curve_is_continuous_2d():
    """Consecutive Hilbert indices map to grid-adjacent cells."""
    b = 4
    g = np.arange(2 ** b)
    xs, ys = np.meshgrid(g, g, indexing="ij")
    cells = np.stack([xs.ravel(), ys.ravel()], 1).astype(np.float64)
    pts = cells / (2 ** b - 1) * (1 - 2 ** -b) + 2 ** -(b + 1)
    keys = hilbert_index_np(pts, bits=b)
    order = np.argsort(keys)
    steps = np.abs(np.diff(cells[order], axis=0)).sum(axis=1)
    assert np.all(steps == 1), "Hilbert curve must step to an adjacent cell"


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31), st.integers(2, 3))
def test_np_jnp_rank_agreement(seed, dim):
    rng = np.random.default_rng(seed)
    pts = rng.uniform(-5, 7, (256, dim))
    k_np = hilbert_index_np(pts)
    k_j = np.asarray(hilbert_index_jnp(jnp.asarray(pts, jnp.float32)))
    r_np = np.argsort(np.argsort(k_np, kind="stable"), kind="stable")
    r_j = np.argsort(np.argsort(k_j, kind="stable"), kind="stable")
    corr = np.corrcoef(r_np, r_j)[0, 1]
    assert corr > 0.99


def test_initial_centers_spread():
    rng = np.random.default_rng(1)
    pts = rng.uniform(0, 1, (10000, 2))
    c = sfc_initial_centers(pts, 16)
    assert c.shape == (16, 2)
    # centers should be well spread: min pairwise distance not tiny
    d = np.linalg.norm(c[:, None] - c[None, :], axis=-1)
    d[np.arange(16), np.arange(16)] = np.inf
    assert d.min() > 0.05


def test_initial_centers_weighted():
    rng = np.random.default_rng(2)
    pts = np.concatenate([rng.uniform(0, 0.1, (1000, 2)),
                          rng.uniform(0.9, 1.0, (1000, 2))])
    w = np.concatenate([np.full(1000, 100.0), np.full(1000, 1.0)])
    c = sfc_initial_centers(pts, 8, w)
    # nearly all centers should sit in the heavy cluster
    assert (c < 0.2).all(axis=1).sum() >= 6
