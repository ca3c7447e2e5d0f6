"""The paper's Maps key set (Kraska et al. 2018, sec. 3.7.1): longitudes
of OpenStreetMap features.

Copied from the program's own generator (``repro.data.gen_maps``) so
that a change to the program cannot change the yardstick.  The shape of
the distribution (the population clusters) is drawn from
``shape_seed``, which the configuration fixes; only the points are
drawn from the run's seed, so every seed serves the same deployment
with other keys.
"""

from __future__ import annotations

import numpy as np


def generate(n: int, seed: int, shape_seed: int) -> np.ndarray:
    """Longitude-like keys in [-180, 180]: 25 population clusters (40% of
    the points) over a uniform base, clipped and deduplicated, f64."""
    shape = np.random.default_rng(shape_seed)
    n_clusters = 25
    centers = shape.uniform(-180, 180, n_clusters)
    widths = shape.uniform(3.0, 20.0, n_clusters)
    weights = shape.dirichlet(np.ones(n_clusters))
    rng = np.random.default_rng(seed)
    n_cluster_pts = int(n * 0.4)
    which = rng.choice(n_clusters, n_cluster_pts, p=weights)
    pts = rng.normal(centers[which], widths[which])
    base = rng.uniform(-180, 180, n - n_cluster_pts)
    keys = np.clip(np.concatenate([pts, base]), -180, 180)
    return np.unique(keys)
