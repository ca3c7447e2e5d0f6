"""The one traffic generator: a mix file's parameters plus the seed give
the whole request stream of a window before it starts.

A mix (``bench/traffic/<mix>.json``) gives

* ``ops``: the share of each request kind (``get``, ``scan``);
* ``keys``: the distribution of the key (or a scan's first key) over
  the stored keys: ``scrambled_zipfian`` or ``latest`` (YCSB's
  generators, with ``theta``);
* ``scan_rows``: ``[min, max]``, a scan's length in stored rows, drawn
  uniformly; the scan is ``[key[i], key[i + length])``;
* ``arrival``: ``poisson``;
* ``rate_ops_s``: the offered rate.

Every seed gets the same number of requests of each kind
(``rate_ops_s * seconds``, split by share); the seed draws their order,
keys, lengths and arrival times.  Arrivals are a Poisson process given
its count: sorted uniform times.
"""

from __future__ import annotations

import dataclasses

import numpy as np

KINDS = ("get", "scan")

# YCSB's scrambled zipfian draws over this many items and hashes into
# the key space
YCSB_ITEM_COUNT = 10_000_000_000


@dataclasses.dataclass
class Plan:
    """One window's requests, in arrival order."""

    due: np.ndarray     # (N,) seconds after the window opens
    kind: np.ndarray    # (N,) index into KINDS
    lo: np.ndarray      # (N,) f64 key (get) or first key (scan)
    hi: np.ndarray      # (N,) f64 scan end (exclusive); nan for get

    @property
    def size(self) -> int:
        return int(self.due.size)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream per purpose, from any whole-number seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} i^-theta: exact for the first million terms, then
    Euler-Maclaurin (error far below float64 rounding of the sum)."""
    m = min(n, 1_000_000)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n == m:
        return head
    f = lambda x: x ** -theta                      # noqa: E731
    df = lambda x: -theta * x ** (-theta - 1)      # noqa: E731
    integral = (n ** (1 - theta) - m ** (1 - theta)) / (1 - theta)
    return head + integral + (f(n) - f(m)) / 2 + (df(n) - df(m)) / 12


def zipfian(u: np.ndarray, items: int, theta: float) -> np.ndarray:
    """YCSB's ZipfianGenerator (Gray et al.) for uniforms ``u``: item
    ranks in [0, items), 0 the most popular."""
    zetan = zeta(items, theta)
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (
        1 - (1 + 0.5 ** theta) / zetan)
    uz = u * zetan
    ret = np.floor(items * (eta * u - eta + 1) ** alpha)
    ret = np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** theta, 1, ret))
    return np.minimum(ret, items - 1).astype(np.int64)


def fnv64(v: np.ndarray) -> np.ndarray:
    """YCSB's Utils.fnvhash64 over the 8 bytes of each value, as a
    non-negative int64."""
    h = np.full(v.shape, 0xCBF29CE484222325, np.uint64)
    x = v.astype(np.uint64)
    prime = np.uint64(1099511628211)
    for _ in range(8):
        h ^= x & np.uint64(0xFF)
        h *= prime
        x >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def draw_items(spec: dict, count: int, items: int,
               rng: np.random.Generator) -> np.ndarray:
    """``count`` item indices in [0, items) by the mix's key
    distribution."""
    kind = spec["dist"]
    u = rng.random(count)
    if kind == "scrambled_zipfian":
        z = zipfian(u, YCSB_ITEM_COUNT, float(spec["theta"]))
        return fnv64(z) % items
    if kind == "latest":
        return items - 1 - zipfian(u, items, float(spec["theta"]))
    raise ValueError(f"unknown key distribution {kind!r}")


def arrival_times(spec: dict, count: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival times in [0, seconds) of a Poisson process with
    ``count`` arrivals."""
    if spec.get("process") != "poisson":
        raise ValueError(f"unknown arrival process {spec.get('process')!r}")
    return np.sort(rng.random(count)) * seconds


def make_plan(mix: dict, keys: np.ndarray, seed: int, seconds: float,
              rate: float = None, stream: int = 0) -> Plan:
    """The request stream of one window.  ``stream`` separates the
    window's draws from the warm-up's."""
    rate = float(mix["rate_ops_s"] if rate is None else rate)
    n_total = max(1, int(round(rate * seconds)))
    shares = {k: float(v) for k, v in mix["ops"].items()}
    unknown = set(shares) - set(KINDS)
    if unknown:
        raise ValueError(f"unknown request kinds {sorted(unknown)}")
    total = sum(shares.values())
    counts = {k: int(round(n_total * v / total)) for k, v in shares.items()}
    rng = rng_for(seed, 10 + stream)
    kind = np.concatenate([np.full(c, KINDS.index(k), np.int8)
                           for k, c in counts.items()])
    kind = rng.permutation(kind)
    n = kind.size
    lo = np.empty(n, np.float64)
    hi = np.full(n, np.nan)
    is_get = kind == KINDS.index("get")
    lo[is_get] = keys[draw_items(mix["keys"], int(is_get.sum()), keys.size,
                                 rng)]
    is_scan = ~is_get
    if is_scan.any():
        r0, r1 = mix["scan_rows"]
        m = int(is_scan.sum())
        length = rng.integers(int(r0), int(r1) + 1, m)
        start = draw_items(mix["keys"], m, keys.size - int(r1) - 1, rng)
        lo[is_scan] = keys[start]
        hi[is_scan] = keys[start + length]
    due = arrival_times(mix["arrival"], n, seconds, rng)
    return Plan(due=due, kind=kind, lo=lo, hi=hi)
