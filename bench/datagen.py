"""Key sets of the benchmark's deployments, made from a seed.

Copied from the program's own generators (``repro.data.gen_maps`` and
``gen_weblogs``) so that a change to the program cannot change the
yardstick.  Two departures, both for steadier runs:

* The shape of the distribution (the map's population clusters, the
  weblog's event days) is drawn from ``shape_seed``, which the
  configuration fixes; only the points are drawn from the run's seed.
  Every seed then serves the same deployment, with other keys.
* ``weblogs`` draws the hour of every request in one vectorized call
  instead of one call per day: the same distribution, faster.
"""

from __future__ import annotations

import numpy as np


def maps(n: int, seed: int, shape_seed: int) -> np.ndarray:
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


def weblogs(n: int, seed: int, shape_seed: int) -> np.ndarray:
    """Unix-timestamp-like keys over 730 days: weekday/weekend and
    semester-break rates, 2% event days at 5x, a bimodal diurnal curve
    with a lunch dip, and sub-second jitter, f64."""
    start = 1_400_000_000
    days = 730
    day = np.arange(days)
    weekday = (day % 7) < 5
    week_rate = np.where(weekday, 1.0, 0.35)
    doy = day % 365
    semester = np.where((doy > 160) & (doy < 240), 0.25, 1.0)  # summer
    semester *= np.where((doy > 350) | (doy < 15), 0.3, 1.0)   # winter
    events = np.random.default_rng(shape_seed).random(days) < 0.02
    rate = week_rate * semester * np.where(events, 5.0, 1.0)
    rate /= rate.sum()
    hours = np.arange(24)
    diurnal = np.exp(-0.5 * ((hours - 10.5) / 2.5) ** 2) + 0.9 * np.exp(
        -0.5 * ((hours - 15.0) / 2.0) ** 2
    )
    diurnal[12] *= 0.55  # lunch
    diurnal[0:6] = 0.15  # overnight crawler/base traffic
    diurnal /= diurnal.sum()

    rng = np.random.default_rng(seed)
    counts = rng.multinomial(n, rate)
    d = np.repeat(np.arange(days, dtype=np.int64), counts)
    hr = rng.choice(24, n, p=diurnal)
    sec = rng.integers(0, 3600, n)
    out = (start + d * 86400 + hr * 3600 + sec).astype(np.float64)
    del d, hr, sec
    out += rng.random(n)  # sub-second uniqueness
    return np.unique(out)


GENERATORS = {"maps": maps, "weblogs": weblogs}
