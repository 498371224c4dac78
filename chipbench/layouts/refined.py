"""The adaptively refined mesh layout: a copy of the program's
``core.meshes.refined_points``."""
import numpy as np


def points(n: int, seed, dim: int = 2) -> np.ndarray:
    """[n, dim] float64 points of an adaptively refined mesh: half the
    points concentrated near a curved feature (a circle of radius 0.3 in
    2-D, a spherical shell in 3-D, both of width 0.02), half uniform in
    the unit cube."""
    rng = np.random.default_rng(seed)
    n_feat = n // 2
    u = rng.uniform(0, 2 * np.pi, n_feat)
    rad = 0.3 + rng.normal(0, 0.02, n_feat)
    if dim == 2:
        feat = np.stack([0.5 + rad * np.cos(u), 0.5 + rad * np.sin(u)], 1)
    elif dim == 3:
        v = np.arccos(rng.uniform(-1, 1, n_feat))
        feat = np.stack([0.5 + rad * np.sin(v) * np.cos(u),
                         0.5 + rad * np.sin(v) * np.sin(u),
                         0.5 + rad * np.cos(v)], 1)
    else:
        raise ValueError(f"refined layout is 2-D or 3-D, got dim={dim}")
    bulk = rng.uniform(0, 1, (n - n_feat, dim))
    return np.concatenate([feat, bulk], axis=0)
