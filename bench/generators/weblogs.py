"""The paper's Weblogs key set (Kraska et al. 2018, sec. 3.7.1): request
timestamps of a university web site over years.

Copied from the program's own generator (``repro.data.gen_weblogs``) so
that a change to the program cannot change the yardstick.  Two
departures, both for steadier runs: the event days are drawn from
``shape_seed``, which the configuration fixes, and only the timestamps
from the run's seed; the hour of every request is drawn in one
vectorized call instead of one call per day (the same distribution,
faster).
"""

from __future__ import annotations

import numpy as np


def generate(n: int, seed: int, shape_seed: int) -> np.ndarray:
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
