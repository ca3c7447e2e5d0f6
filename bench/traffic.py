"""The one traffic generator: a mix file's parameters plus the seed give
the whole request stream of a window before it starts.

A mix (``bench/traffic/<mix>.json``) gives

* ``ops``: the share of each request kind; each kind is a module
  ``bench/ops/<kind>.py`` (below);
* ``keys``: the distribution of the key (or a scan's first key) over
  the stored keys: ``scrambled_zipfian`` or ``latest`` (YCSB's
  generators, with ``theta``);
* ``scan_rows``: ``[min, max]``, a scan's length in stored rows, drawn
  uniformly; the scan is ``[key[i], key[i + length])``;
* ``inserts``: ``{"order": "newest" | "uniform", "warm": n}`` where
  the mix inserts: which keys the build holds back for it, in what
  order they come (`hold_back`), and at least how many the warm-up
  stores (default 1);
* ``arrival``: ``poisson``;
* ``rate_ops_s``: the offered rate.

Every seed gets the same number of requests of each kind
(``rate_ops_s * seconds``, split by share); the seed draws their order,
keys, lengths and arrival times.  Arrivals are a Poisson process given
its count: sorted uniform times.

A kind module gives ``ADDS_KEYS`` (whether its requests store keys) and

* ``draw(mix, count, rng)``: what the plan draws for its requests, in
  a fixed order from the plan's one stream;
* ``place(mix, drawn, space, stored)``: their ``(lo, hi, val)``, once
  every draw is made and ``stored`` (how many keys of ``space`` are
  stored when each request is due) is known;
* ``args(plan, i, page_size)``, ``answer(result)``,
  ``warm_count(mix, max_round)``, ``warm_rounds(idx, max_round)`` and
  ``check(oracle, win, idx, service)``: the call, the answer as the
  client keeps it, how many requests the warm-up needs and how it sends
  them in rounds, and the check of its answers (`bench.loop`,
  `bench.harness`).

A plan's kind codes are the kinds' places in the mix's ``ops``.

Keys are counted in insertion order, as YCSB counts them: the stored
keys in sorted order, then the held-back pool in the order it is
inserted (`KeySpace`).  ``latest`` counts back from the newest key
whose insert is due before the request.
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Dict, Optional, Tuple

import numpy as np

from bench import catalog

# YCSB's scrambled zipfian draws over this many items and hashes into
# the key space
YCSB_ITEM_COUNT = 10_000_000_000


@dataclasses.dataclass
class Plan:
    """One window's requests, in arrival order."""

    due: np.ndarray     # (N,) seconds after the window opens
    kind: np.ndarray    # (N,) index into ``kinds``
    lo: np.ndarray      # (N,) key (get, insert) or first key (scan)
    hi: np.ndarray      # (N,) scan end (exclusive); nan for other kinds
    val: np.ndarray     # (N,) int64 value written; -1 for other kinds
    kinds: Tuple[str, ...]   # the kind of each code, in the mix's order

    @property
    def size(self) -> int:
        return int(self.due.size)


@dataclasses.dataclass(frozen=True)
class KeySpace:
    """The keys a plan draws from.  ``final`` (sorted) holds every key
    stored at the close of the last window; ``pool`` the indices into
    ``final`` of the keys held back for inserts, in insert order.  Item
    ``i`` of the insertion order is the ``i``-th key of ``final`` outside
    the pool while ``i < final.size - pool.size``, else ``pool[i -
    that]``; the first ``stored`` items are stored when the plan opens."""

    final: np.ndarray
    pool: np.ndarray
    stored: int

    @classmethod
    def of(cls, keys: np.ndarray) -> "KeySpace":
        """Every key stored, none held back."""
        return cls(keys, np.empty(0, np.int64), int(keys.size))

    @property
    def base_size(self) -> int:
        return int(self.final.size - self.pool.size)

    def index_of(self, items: np.ndarray) -> np.ndarray:
        """Indices into ``final`` of insertion-order items."""
        items = np.asarray(items, np.int64)
        if not self.pool.size:
            return items
        if np.any(items >= self.final.size):
            raise ValueError("the plan inserts more keys than the pool "
                             f"holds ({self.pool.size})")
        held = np.sort(self.pool)
        shift = held - np.arange(held.size)
        nb = self.base_size
        base = items + np.searchsorted(shift, np.minimum(items, nb - 1),
                                       side="right")
        return np.where(items < nb, base,
                        self.pool[np.clip(items - nb, 0, None)])

    def base(self) -> np.ndarray:
        """Indices into ``final`` of the keys stored when the plan
        opens, ascending."""
        if not self.pool.size:
            return np.arange(self.final.size)
        stored_pool = self.pool[:self.stored - self.base_size]
        keep = np.ones(self.final.size, bool)
        keep[self.pool] = False
        keep[stored_pool] = True
        return np.flatnonzero(keep)

    def after(self, plan: Plan, ops: Dict[str, object]) -> "KeySpace":
        """The space once ``plan``'s writes are stored."""
        adds = sum(int(np.sum(plan.kind == c))
                   for c, k in enumerate(plan.kinds)
                   if k in ops and ops[k].ADDS_KEYS)
        return dataclasses.replace(self, stored=self.stored + adds)


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


def zeta_many(ns: np.ndarray, theta: float) -> np.ndarray:
    """`zeta` of each of ``ns``, which span a short range (the keys
    inserted in one plan): the smallest once, then term by term."""
    ns = np.asarray(ns, np.int64)
    n0 = int(ns.min())
    terms = np.arange(n0 + 1, int(ns.max()) + 1, dtype=np.float64) ** -theta
    table = zeta(n0, theta) + np.concatenate([[0.0], np.cumsum(terms)])
    return table[ns - n0]


def zipfian(u: np.ndarray, items, theta: float) -> np.ndarray:
    """YCSB's ZipfianGenerator (Gray et al.) for uniforms ``u``: item
    ranks in [0, items), 0 the most popular.  ``items`` may be one count
    or one per draw (a key set that grows by inserts)."""
    zetan = (zeta(items, theta) if np.ndim(items) == 0
             else zeta_many(items, theta))
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / items) ** (1 - theta)) / (
        1 - (1 + 0.5 ** theta) / zetan)
    uz = u * zetan
    ret = np.floor(items * (eta * u - eta + 1) ** alpha)
    ret = np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** theta, 1, ret))
    return np.minimum(ret, np.asarray(items) - 1).astype(np.int64)


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


def items_of(spec: dict, u: np.ndarray, items) -> np.ndarray:
    """Item indices in [0, items) for uniforms ``u`` by the mix's key
    distribution; ``items`` is one count or one per draw."""
    kind = spec["dist"]
    if kind == "scrambled_zipfian":
        z = zipfian(u, YCSB_ITEM_COUNT, float(spec["theta"]))
        return fnv64(z) % items
    if kind == "latest":
        return np.asarray(items) - 1 - zipfian(u, items,
                                               float(spec["theta"]))
    raise ValueError(f"unknown key distribution {kind!r}")


def arrival_times(spec: dict, count: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival times in [0, seconds) of a Poisson process with
    ``count`` arrivals."""
    if spec.get("process") != "poisson":
        raise ValueError(f"unknown arrival process {spec.get('process')!r}")
    return np.sort(rng.random(count)) * seconds


def kind_counts(mix: dict, seconds: float, rate: float = None
                ) -> Dict[str, int]:
    """Requests of each kind in a window: the same for every seed."""
    rate = float(mix["rate_ops_s"] if rate is None else rate)
    n_total = max(1, int(round(rate * seconds)))
    shares = {k: float(v) for k, v in mix["ops"].items()}
    total = sum(shares.values())
    return {k: int(round(n_total * v / total)) for k, v in shares.items()}


def hold_back(mix: dict, final: np.ndarray, count: int,
              seed: int) -> np.ndarray:
    """Indices into ``final`` of the ``count`` keys the build holds back
    for the mix's inserts, in insert order: the largest in ascending
    order (``newest``: time-ordered ingest) or a subset drawn from the
    seed in random order (``uniform``: YCSB's hashed insert order)."""
    if count <= 0:
        return np.empty(0, np.int64)
    order = mix.get("inserts", {}).get("order")
    if order == "newest":
        return np.arange(final.size - count, final.size, dtype=np.int64)
    if order == "uniform":
        return rng_for(seed, 3).choice(final.size, count, replace=False)
    raise ValueError(f"unknown insert order {order!r}")


def load_ops(mix: dict, root: pathlib.Path = catalog.ROOT
             ) -> Dict[str, object]:
    """The kind modules of a mix's ``ops``; ValueError for a kind with
    no module."""
    try:
        return catalog.load_kinds(mix["ops"], root)
    except LookupError as e:
        raise ValueError(f"unknown request kinds in {sorted(mix['ops'])}: "
                         f"{e}") from None


def blank(n: int, dtype) -> np.ndarray:
    """A column for requests that have no such key: nan where the key
    type has one, else 0."""
    if np.issubdtype(dtype, np.floating):
        return np.full(n, np.nan, dtype)
    return np.zeros(n, dtype)


def make_plan(mix: dict, space: KeySpace, seed: int, seconds: float,
              rate: float = None, stream: int = 0,
              ops: Optional[Dict[str, object]] = None) -> Plan:
    """The request stream of one window over ``space``.  ``stream``
    separates the window's draws from the warm-up's."""
    ops = load_ops(mix) if ops is None else ops
    counts = kind_counts(mix, seconds, rate)
    kinds = tuple(counts)
    rng = rng_for(seed, 10 + stream)
    kind = np.concatenate([np.full(c, kinds.index(k), np.int8)
                           for k, c in counts.items()])
    kind = rng.permutation(kind)
    n = kind.size
    present = [(c, k) for c, k in enumerate(kinds) if counts[k]]
    drawn = {k: ops[k].draw(mix, int(np.sum(kind == c)), rng)
             for c, k in present}
    due = arrival_times(mix["arrival"], n, seconds, rng)
    adds = np.zeros(n, np.int64)
    for c, k in present:
        if ops[k].ADDS_KEYS:
            adds[kind == c] = 1
    if adds.any():   # the keys stored when each request is due
        stored = space.stored + np.cumsum(adds) - adds
    else:
        stored = np.full(n, space.stored)
    lo = np.empty(n, space.final.dtype)
    hi = blank(n, space.final.dtype)
    val = np.full(n, -1, np.int64)
    for c, k in present:
        mine = kind == c
        # one count where nothing is inserted: the draws' arithmetic as
        # plans always had it
        at = stored[mine] if adds.any() else space.stored
        k_lo, k_hi, k_val = ops[k].place(mix, drawn[k], space, at)
        lo[mine] = k_lo
        if k_hi is not None:
            hi[mine] = k_hi
        if k_val is not None:
            val[mine] = k_val
    return Plan(due=due, kind=kind, lo=lo, hi=hi, val=val, kinds=kinds)
