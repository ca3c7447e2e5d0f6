#!/usr/bin/env python3
"""Bring-up smoke run of the learned-index service on a TPU.

    python chip_smoke.py             # one chip: default path + kernel path
    python chip_smoke.py --chips 4   # K=4 shards over four chips only

Drives the main path a user calls — `IndexFrontend` -> `IndexService` /
`ShardedIndexService` -> `DevicePlane` -> kernel — and checks every
answer against plain NumPy (`np.searchsorted` over the same live keys).
Exact surfaces (`get`, `contains`, `range_lookup`, host `scan`) are
compared exactly; device-frame surfaces (`lookup_batch`, `scan_batch`)
are compared in the normalized float32 frame they promise.

Phases on one chip, in this order:

  kernel   strategies ``pallas``, ``pallas_fused`` and
           ``sharded_fused`` plus ``scan_batch`` on both services, at
           the largest key count whose kernels the TPU compiler accepts
           (found here by compiling); afterwards no kernel may have
           failed over and every dispatch row of these strategies must
           be a kernel row.
  default  200M map-shaped keys (`data.gen_maps`, the paper's Maps
           size) under the default `ServiceConfig` (strategy
           ``binary``, the XLA path): mixed rounds through the frontend
           with a compaction and snapshot swap between rounds.

``--chips 4`` runs only the four-chip path: a K=4 `ShardedIndexService`
whose shard rows sit one per chip, against the same service on one chip
and the oracle.

Exits non-zero if any check fails or no TPU is present.  The last line
of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
PAPER_KEYS = 200_000_000      # the paper's Maps data set
HOST_BYTES_PER_KEY = 150      # peak host memory of the default phase
ROUNDS = 2
BATCH = 4096                  # keys per get / contains / lookup_batch
WRITES = 1024                 # inserts and deletes per round
SCAN_ROWS = 3000              # rows per scanned interval
PAGE = 256
KERNEL_STRATEGIES = ("pallas", "pallas_fused", "sharded_fused")
FOUR_CHIP_KEYS = 1 << 25
LATTICE = 360.0 / 2 ** 23     # kernel-phase key grid (f32-injective)


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    def __init__(self):
        self.failed = []
        self.passed = 0

    def expect(self, name: str, ok, detail: str = "") -> None:
        if bool(ok):
            self.passed += 1
        else:
            self.failed.append(name)
            log(f"FAIL {name} {detail}")


# ---------------------------------------------------------------------------
# the oracle: plain NumPy over the same live keys
# ---------------------------------------------------------------------------

class Oracle:
    """Live keys = base minus deletes plus inserts, as sorted NumPy
    arrays.  Deletes are base keys (never the two ends, so the
    service's normalization frame stays put); inserts are fresh keys
    strictly inside it."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.base, self.base_vals = keys, vals
        self.dead = np.empty(0)
        self.ins, self.ins_vals = np.empty(0), np.empty(0, np.int64)
        self.lo, self.hi = float(keys[0]), float(keys[-1])
        self._base32 = None

    def norm(self, x) -> np.ndarray:
        x = np.asarray(x, np.float64)
        return ((x - self.lo) / (self.hi - self.lo)).astype(np.float32)

    def apply(self, ins, ins_vals, dels) -> None:
        self.dead = np.sort(np.concatenate([self.dead, dels]))
        keys = np.concatenate([self.ins, ins])
        order = np.argsort(keys)
        self.ins = keys[order]
        self.ins_vals = np.concatenate([self.ins_vals, ins_vals])[order]

    @staticmethod
    def _has(sorted_arr, q) -> np.ndarray:
        i = np.searchsorted(sorted_arr, q)
        return (i < sorted_arr.size) & (
            sorted_arr[np.minimum(i, max(sorted_arr.size - 1, 0))] == q
        ) if sorted_arr.size else np.zeros(np.shape(q), bool)

    def rank(self, q) -> np.ndarray:
        return (np.searchsorted(self.base, q) - np.searchsorted(self.dead, q)
                + np.searchsorted(self.ins, q))

    def member(self, q) -> np.ndarray:
        return ((self._has(self.base, q) & ~self._has(self.dead, q))
                | self._has(self.ins, q))

    def rank32(self, q) -> np.ndarray:
        """Rank in the normalized float32 frame: live keys whose f32
        image sorts below q's."""
        if self._base32 is None:
            self._base32 = self.norm(self.base)
        qn = self.norm(q)
        return (np.searchsorted(self._base32, qn)
                - np.searchsorted(self.norm(self.dead), qn)
                + np.searchsorted(self.norm(self.ins), qn))

    def rows(self, lo: float, hi: float):
        i0, i1 = np.searchsorted(self.base, [lo, hi])
        k, v = self.base[i0:i1], self.base_vals[i0:i1]
        keep = ~self._has(self.dead, k)
        j0, j1 = np.searchsorted(self.ins, [lo, hi])
        k = np.concatenate([k[keep], self.ins[j0:j1]])
        v = np.concatenate([v[keep], self.ins_vals[j0:j1]])
        order = np.argsort(k)
        return k[order], v[order]

    def live_base_sample(self, rng, count: int) -> np.ndarray:
        """Base keys still live, never the two frame ends."""
        out = np.empty(0)
        while out.size < count:
            k = self.base[rng.integers(1, self.base.size - 1, 2 * count)]
            out = np.unique(np.concatenate([out, k[~self._has(self.dead, k)]]))
        return rng.permutation(out)[:count]


# ---------------------------------------------------------------------------
# one round through the frontend
# ---------------------------------------------------------------------------

def serve_round(fe, svc, oracle, rng, fresh, next_id, checks, tag,
                scan_frame=None):
    """Writes, then every read surface, each compared with the oracle.
    ``scan_frame`` maps raw keys into the frame `scan_batch` rows use
    (None: the service's own normalization, i.e. `oracle.norm`)."""
    ins = fresh(WRITES)
    ids = np.arange(next_id, next_id + ins.size, dtype=np.int64)
    dels = oracle.live_base_sample(rng, WRITES)
    checks.expect(f"{tag}.insert", fe.insert("writer", ins, ids) == ins.size)
    checks.expect(f"{tag}.delete", fe.delete("writer", dels) == dels.size)
    oracle.apply(ins, ids, dels)

    q = np.concatenate([
        oracle.live_base_sample(rng, BATCH // 4), rng.permutation(ins),
        dels, rng.uniform(oracle.lo, oracle.hi, BATCH // 4),
    ])[:BATCH]
    rank, found = fe.get("reader", q)
    checks.expect(f"{tag}.get.rank", np.array_equal(rank, oracle.rank(q)))
    checks.expect(f"{tag}.get.found", np.array_equal(found, oracle.member(q)))
    checks.expect(f"{tag}.contains",
                  np.array_equal(fe.contains("reader", q), oracle.member(q)))

    i = int(rng.integers(1, oracle.base.size - SCAN_ROWS - 1))
    lo, hi = float(oracle.base[i]), float(oracle.base[i + SCAN_ROWS])
    got = fe.range_lookup("reader", lo, hi)
    want = tuple(int(r) for r in oracle.rank(np.array([lo, hi])))
    checks.expect(f"{tag}.range", tuple(got) == want, f"{got} != {want}")

    keys, vals = oracle.rows(lo, hi)
    pages = list(svc.scan(lo, hi, PAGE))
    hk = np.concatenate([p.keys[p.live_mask] for p in pages] or [[]])
    hv = np.concatenate([p.vals[p.live_mask] for p in pages] or [[]])
    checks.expect(f"{tag}.scan", np.array_equal(hk, keys)
                  and np.array_equal(hv, vals), f"{hk.size} vs {keys.size}")

    # scan_batch through the frontend: device frame
    dk, dv, live = (np.asarray(a) for a in fe.scan("reader", lo, hi, PAGE))
    dk, dv = dk[live], dv[live]
    if scan_frame is None:
        # the live rows whose f32 image falls in [f32(lo), f32(hi)).
        # Payloads are promised only where that image is unique: rows
        # tied in f32 may come back in either source's order
        pad = (oracle.hi - oracle.lo) * 2.0 ** -20
        wk, wv = oracle.rows(lo - pad, hi + pad)
        w32 = oracle.norm(wk)
        lo32, hi32 = oracle.norm([lo, hi])
        sel = (w32 >= lo32) & (w32 < hi32)
        want_k, want_v = w32[sel], wv[sel].astype(np.int32)
        checks.expect(f"{tag}.scan_batch.keys",
                      np.array_equal(dk, want_k),
                      f"{dk.size} vs {want_k.size}")
        if dk.size == want_k.size:
            u, counts = np.unique(want_k, return_counts=True)
            single = np.isin(want_k, u[counts == 1])
            checks.expect(f"{tag}.scan_batch.vals",
                          np.array_equal(dv[single], want_v[single]))
    else:
        checks.expect(f"{tag}.scan_batch.keys",
                      np.array_equal(dk, scan_frame(keys)),
                      f"{dk.size} vs {keys.size}")
        checks.expect(f"{tag}.scan_batch.vals",
                      np.array_equal(dv, vals.astype(np.int32)))

    qb = oracle.live_base_sample(rng, BATCH)
    got = np.asarray(svc.lookup_batch(qb))
    checks.expect(f"{tag}.lookup_batch", np.array_equal(got, oracle.rank32(qb)))
    return next_id + ins.size


def fresh_keys(oracle, rng, lattice=None):
    """Fresh keys strictly inside the frame, never stored before."""
    def draw(count):
        out = np.empty(0)
        while out.size < count:
            k = rng.uniform(oracle.lo, oracle.hi, 2 * count)
            if lattice is not None:
                k = np.round(k / lattice) * lattice
            k = k[(k > oracle.lo) & (k < oracle.hi)]
            k = k[~oracle.member(k) & ~Oracle._has(oracle.dead, k)]
            out = np.unique(np.concatenate([out, k]))
        return rng.permutation(out)[:count]
    return draw


def run_rounds(svc, keys, vals, checks, tag, *, lattice=None,
               scan_frame=None):
    """Warm up, then ROUNDS mixed rounds with a compaction + snapshot
    swap between rounds."""
    from repro.serve.frontend import IndexFrontend

    rng = np.random.default_rng(SEED + 1)
    oracle = Oracle(keys, vals)
    fresh = fresh_keys(oracle, rng, lattice)
    next_id = keys.size
    with IndexFrontend(svc) as fe:
        t = time.perf_counter()
        next_id = serve_round(fe, svc, oracle, rng, fresh, next_id, checks,
                              f"{tag}.warmup", scan_frame)
        log(f"{tag}: warm-up round {time.perf_counter() - t:.1f} s "
            "(compiles included)")
        for r in range(ROUNDS):
            v0 = svc.version
            t = time.perf_counter()
            svc.flush()
            checks.expect(f"{tag}.swap{r}", svc.version > v0)
            t1 = time.perf_counter()
            next_id = serve_round(fe, svc, oracle, rng, fresh, next_id,
                                  checks, f"{tag}.round{r}", scan_frame)
            log(f"{tag}: compaction {t1 - t:.1f} s, round {r} "
                f"{time.perf_counter() - t1:.1f} s")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def default_keys() -> int:
    """The paper's size, cut to the largest power of two the host can
    hold when it cannot hold that."""
    avail = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    n = PAPER_KEYS
    if n * HOST_BYTES_PER_KEY > avail:
        n = 1 << int(np.log2(avail / HOST_BYTES_PER_KEY))
        log(f"default: cut to {n} keys (host has {avail / 2**30:.1f} GiB "
            f"available, {HOST_BYTES_PER_KEY} B/key needed)")
    return n


def default_phase(checks, n_keys: int) -> None:
    from repro.data import gen_maps
    from repro.index_service import IndexService

    t = time.perf_counter()
    keys = gen_maps(n_keys, seed=SEED)
    vals = np.arange(keys.size, dtype=np.int64)
    t1 = time.perf_counter()
    svc = IndexService(keys, vals=vals)
    log(f"default: {keys.size} map keys (gen_maps n={n_keys}, seed={SEED}); "
        f"generate {t1 - t:.1f} s, build {time.perf_counter() - t1:.1f} s, "
        f"strategy {svc.config.strategy}")
    run_rounds(svc, keys, vals, checks, "default")


def lattice_maps(n: int) -> np.ndarray:
    """``n`` map-shaped keys snapped to a grid of 2^23 steps over
    [-180, 180]: distinct keys sit two float32 ulps apart in any
    normalization frame inside that span, so the device frame of the
    kernel phase is exact."""
    from repro.data import gen_maps

    grid = np.unique(np.round(gen_maps(3 * n, seed=SEED) / LATTICE))
    rng = np.random.default_rng(SEED)
    return np.sort(rng.choice(grid, n, replace=False)) * LATTICE


def kernel_key_limit() -> int:
    """Largest power-of-two key count at which every RMI kernel compiles
    for this chip: the kernels hold keys, payloads, leaves and the
    delta as whole VMEM operands, so the core's scoped VMEM bounds the
    index.  Shapes follow the services: n // 64 leaves, 4 shards."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import rmi_lookup as rl

    f32, i32 = jnp.float32, jnp.int32
    d, b, s = 8192, BATCH, 4

    def sds(*shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt)

    def programs(n):
        m, ns = n // 64, n // s
        s0 = (sds(1, 1), sds(1))
        leaves = [sds(m)] * 4
        yield rl.rmi_merged_lookup_pallas, (
            sds(b), s0, *leaves, sds(n), sds(d), sds(d + 1, dt=i32),
        ), dict(hidden=(), n=n, num_leaves=m, max_window=256)
        yield rl.rmi_sharded_merged_lookup_pallas, (
            sds(s, b), (sds(s, 1, 1), sds(s, 1)),
            *[sds(s, ns // 48)] * 4, sds(s, ns), sds(s, d),
            sds(s, d + 1, dt=i32), sds(s, dt=i32), sds(s, dt=i32), sds(s),
        ), dict(hidden=(), max_window=256)
        yield rl.rmi_scan_range_pallas, (
            sds(2), sds(n), sds(n, dt=i32), sds(n + 1, dt=i32), sds(d),
            sds(d, dt=i32), sds(d, dt=i32),
        ), dict(page_size=PAGE, max_pages=16)
        yield rl.rmi_scan_page_pallas, (
            sds(16, dt=i32), sds(n), sds(n, dt=i32), sds(d),
            sds(d, dt=i32), sds(d, dt=i32), sds(1, dt=i32),
        ), dict(page_size=PAGE)
        yield rl.rmi_sharded_scan_page_pallas, (
            sds(s, ns), sds(s, ns, dt=i32), sds(s, ns + 1, dt=i32),
            sds(s, d), sds(s, d, dt=i32), sds(s, d, dt=i32),
            sds(s, dt=i32), sds(s, dt=i32), sds(s, dt=i32),
        ), dict(page_size=PAGE, max_pages=16)

    def fits(n: int) -> bool:
        try:
            for fn, args, kw in programs(n):
                fn.lower(*args, interpret=False, **kw).compile()
            return True
        except Exception as e:  # noqa: BLE001 — a VMEM refusal is the answer
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return False

    n = 1 << 24
    while n > 1 << 12 and not fits(n):
        n //= 2
    return n


def kernel_phase(checks) -> None:
    import jax
    from repro.index_service import (
        IndexService,
        ServiceConfig,
        ShardedIndexService,
    )
    from repro.kernels import ops

    t = time.perf_counter()
    limit = kernel_key_limit()
    log(f"kernel: VMEM limit {limit} keys (largest power of two whose "
        f"kernels compile for {jax.devices()[0].device_kind}; found in "
        f"{time.perf_counter() - t:.1f} s)")
    keys = lattice_maps(limit)
    vals = np.arange(keys.size, dtype=np.int64)
    ops.reset_dispatch_stats()
    ops.reset_failover()
    for strategy in KERNEL_STRATEGIES:
        t = time.perf_counter()
        svc = IndexService(keys, ServiceConfig(strategy=strategy), vals=vals)
        log(f"kernel: IndexService strategy={strategy} {keys.size} keys, "
            f"build {time.perf_counter() - t:.1f} s")
        run_rounds(svc, keys, vals, checks, f"kernel.{strategy}",
                   lattice=LATTICE)
    t = time.perf_counter()
    svc = ShardedIndexService(
        keys, ServiceConfig(strategy="sharded_fused", num_shards=4),
        vals=vals,
    )
    log(f"kernel: ShardedIndexService K=4 strategy=sharded_fused, build "
        f"{time.perf_counter() - t:.1f} s")
    run_rounds(svc, keys, vals, checks, "kernel.sharded", lattice=LATTICE,
               scan_frame=svc.scan_normalize)

    fo = ops.failover_summary()
    failed_over = {k: v for k, v in fo.items()
                   if v["disabled"] or v["fallback_calls"]}
    checks.expect("kernel.no_failover", not failed_over, str(failed_over))
    rows = [r for r in ops.dispatch_summary()["rows"]
            if r["strategy"] in KERNEL_STRATEGIES]
    paths = sorted({(r["op"], r["strategy"], r["path"]) for r in rows})
    log(f"kernel: failover pairs exercised {sorted(fo)}; failed over: "
        f"{failed_over or 'none'}")
    log(f"kernel: dispatch rows {paths}")
    checks.expect("kernel.rows", rows and all(r["path"] == "kernel"
                                              for r in rows), str(paths))


def four_chip_phase(checks) -> None:
    import jax
    from repro.data import gen_maps
    from repro.index_service import ServiceConfig, ShardedIndexService

    keys = gen_maps(FOUR_CHIP_KEYS, seed=SEED)
    vals = np.arange(keys.size, dtype=np.int64)
    cfg = ServiceConfig(num_shards=4)
    t = time.perf_counter()
    spread = ShardedIndexService(keys, cfg, vals=vals)
    local = ShardedIndexService(keys, cfg, vals=vals)
    local._shard_mesh = lambda: None  # the same service held on one chip
    log(f"four-chip: {keys.size} map keys, K=4 strategy "
        f"{cfg.strategy}; two builds {time.perf_counter() - t:.1f} s")

    plan = spread._device_plan()
    for name in ("keys", "leaf_w", "leaf_b", "err_lo", "err_hi", "shard_n"):
        arr = getattr(plan, name)
        log(f"four-chip: {name} {tuple(arr.shape)} on "
            f"{len(arr.sharding.device_set)} devices")
    checks.expect("four.spread", len(plan.keys.sharding.device_set) == 4)
    checks.expect("four.local",
                  len(local._device_plan().keys.sharding.device_set) == 1)

    rng = np.random.default_rng(SEED + 2)
    oracle = Oracle(keys, vals)
    for r in range(ROUNDS):
        q = np.concatenate([oracle.live_base_sample(rng, BATCH // 2),
                            rng.uniform(oracle.lo, oracle.hi, BATCH // 2)])
        a, b = spread.get(q), local.get(q)
        checks.expect(f"four.round{r}.same", np.array_equal(a[0], b[0])
                      and np.array_equal(a[1], b[1]))
        checks.expect(f"four.round{r}.oracle",
                      np.array_equal(a[0], oracle.rank(q))
                      and np.array_equal(a[1], oracle.member(q)))
        qb = oracle.live_base_sample(rng, BATCH)
        checks.expect(f"four.round{r}.lookup_batch", np.array_equal(
            np.asarray(spread.lookup_batch(qb)),
            np.asarray(local.lookup_batch(qb))))
    log(f"four-chip: {ROUNDS} rounds of {BATCH} gets and lookups, "
        f"{jax.device_count()} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")
    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(checks)
    else:
        with jax.default_device(devices[0]):
            kernel_phase(checks)
            log(f"kernel phase done at {time.perf_counter() - t0:.1f} s")
            default_phase(checks, default_keys())
    log(f"checks: {checks.passed} passed, {len(checks.failed)} failed "
        f"in {time.perf_counter() - t0:.1f} s")
    if checks.failed:
        log(f"failed: {checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": args.chips,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
