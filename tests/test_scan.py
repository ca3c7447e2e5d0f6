"""Scan subsystem: paged (keys, vals, live_mask) streams over
base+delta merge order, pinned to a NumPy merge oracle.

The load-bearing guarantees:

  * `scan` pages concatenated equal a plain NumPy merge of (base minus
    tombstones, plus staged inserts) — through heavy interleaved churn,
    at K in {1, 3, 8}, across per-shard compactions and rebalances
    (tier-1 runs a reduced op count; the >= 100k-op sweep rides in the
    nightly slow job);
  * an OPEN iterator is snapshot-pinned: inserts/deletes (and the
    compactions/rebalances they trigger) between pages never tear it —
    it keeps answering for the key set as of `scan()` time;
  * the Pallas scan-page kernel and its XLA fallback are bit-identical
    for ANY query — pads, empty pages, ranks past the end;
  * page boundaries behave at non-multiple sizes, and empty/inverted
    ranges yield no pages.
"""

import functools

import numpy as np
import pytest

from repro.index_service import (
    IndexService,
    ServiceConfig,
    ShardedIndexService,
)
from repro.kernels import ops

KS = (1, 3, 8)


def _concat(pages):
    pages = list(pages)
    if not pages:
        return np.empty(0), np.empty(0, np.int64)
    keys = np.concatenate([p.keys[p.live_mask] for p in pages])
    vals = np.concatenate([p.vals[p.live_mask] for p in pages])
    # every page but the last must be full, and pads must be inert
    for p in pages[:-1]:
        assert p.count == p.live_mask.size
    for p in pages:
        assert np.isinf(p.keys[~p.live_mask]).all()
        assert (p.vals[~p.live_mask] == 0).all()
    return keys, vals


def _oracle_slice(live, lo, hi):
    arr = np.array(sorted(live))
    vals = np.array([live[k] for k in arr], np.int64)
    m = (arr >= lo) & (arr < hi)
    return arr[m], vals[m]


# --------------------------------------------------------------------------
# the acceptance gate: scan == NumPy merge under interleaved churn
# --------------------------------------------------------------------------

def _churn_scan(total_target, n_base, k, delta_capacity=768,
                page_size=113):
    """Interleaved inserts/deletes with scans between batches — and
    WITHIN open iterators — all checked against one dict oracle."""
    rng = np.random.default_rng(k + 17)
    base = np.unique(rng.integers(0, 1 << 48, n_base).astype(np.float64))
    bvals = rng.integers(0, 1 << 30, base.size)
    ctor = (
        (lambda: IndexService(
            base, ServiceConfig(delta_capacity=delta_capacity),
            vals=bvals))
        if k == 1 else
        (lambda: ShardedIndexService(
            base, ServiceConfig(num_shards=k, delta_capacity=delta_capacity),
            vals=bvals))
    )
    svc = ctor()
    live = dict(zip(base.tolist(), bvals.tolist()))

    total_ops = 0
    batch = 0
    while total_ops < total_target:
        # fresh keys only (value semantics for re-inserting a live key
        # are level-dependent; churn sticks to the well-defined path)
        ins = np.unique(rng.integers(0, 1 << 48, 500).astype(np.float64))
        ins = ins[~np.isin(ins, np.array(sorted(live)))]
        iv = rng.integers(0, 1 << 30, ins.size)
        svc.insert(ins, iv)
        live.update(zip(ins.tolist(), iv.tolist()))
        arr = np.array(sorted(live))
        dels = rng.choice(arr, 300, replace=False)
        svc.delete(dels)
        for x in dels:
            del live[float(x)]
        total_ops += ins.size + dels.size
        batch += 1
        if batch % 3 != 0:
            continue
        arr = np.array(sorted(live))
        lo = float(arr[int(rng.integers(0, arr.size // 2))])
        hi = float(arr[int(rng.integers(arr.size // 2, arr.size))])
        # plain scan vs oracle
        got_k, got_v = _concat(svc.scan(lo, hi, page_size))
        want_k, want_v = _oracle_slice(live, lo, hi)
        np.testing.assert_array_equal(got_k, want_k)
        np.testing.assert_array_equal(got_v, want_v)
        # open iterator survives concurrent churn (pinned view)
        it = svc.scan(lo, hi, page_size)
        consumed = [p for _, p in zip(range(2), it)]
        mut_ins = np.unique(
            rng.integers(0, 1 << 48, 200).astype(np.float64)
        )
        mut_ins = mut_ins[~np.isin(mut_ins, np.array(sorted(live)))]
        svc.insert(mut_ins)
        live.update((k2, 0) for k2 in mut_ins.tolist())
        arr = np.array(sorted(live))
        mut_del = rng.choice(arr, 150, replace=False)
        svc.delete(mut_del)
        for x in mut_del:
            del live[float(x)]
        total_ops += mut_ins.size + mut_del.size
        got_k, got_v = _concat(consumed + list(it))
        np.testing.assert_array_equal(got_k, want_k)  # pin-time view
        np.testing.assert_array_equal(got_v, want_v)
    assert svc.stats_summary()["scan"]["pages"] > 0
    return svc


@pytest.mark.parametrize("k", KS)
def test_scan_churn_quick_vs_numpy_merge(k):
    _churn_scan(6_000, 6_000, k)


@pytest.mark.slow
@pytest.mark.parametrize("k", KS)
def test_scan_churn_100k_vs_numpy_merge(k):
    _churn_scan(100_000, 30_000, k, delta_capacity=4096, page_size=509)


def test_scan_survives_rebalance_mid_scan():
    """A rebalance between pages of an open sharded iterator must not
    tear it: the pinned per-shard views answer for scan-time state."""
    rng = np.random.default_rng(5)
    base = np.unique(rng.integers(0, 1 << 40, 8_000).astype(np.float64))
    svc = ShardedIndexService(base, ServiceConfig(
        num_shards=4, delta_capacity=4096, shard_balance_factor=2.0,
    ))
    lo, hi = float(base[100]), float(base[-100])
    want = base[(base >= lo) & (base < hi)]
    it = svc.scan(lo, hi, 97)
    first = [p for _, p in zip(range(3), it)]
    # hot-tail insert: routes everything to the last shard -> rebalance
    hot = base.max() + 1.0 + np.arange(30_000, dtype=np.float64)
    svc.insert(hot)
    assert svc.stats["rebalances"] >= 1
    got_k, _ = _concat(first + list(it))
    np.testing.assert_array_equal(got_k, want)
    # a fresh scan sees the new keys
    got2, _ = _concat(svc.scan(lo, float(hot[-1]) + 1.0, 1024))
    want2 = np.concatenate([base[base >= lo], hot])
    np.testing.assert_array_equal(got2, want2)


# --------------------------------------------------------------------------
# page geometry: boundaries, non-multiples, empty ranges
# --------------------------------------------------------------------------

def test_scan_page_boundaries_and_empty_ranges():
    base = np.arange(0, 1000, dtype=np.float64) * 2.0
    vals = np.arange(1000, dtype=np.int64) * 7
    svc = IndexService(base, ServiceConfig(delta_capacity=128), vals=vals)
    svc.delete(base[::5])
    live_k = base[np.arange(1000) % 5 != 0]
    live_v = vals[np.arange(1000) % 5 != 0]
    for page_size in (1, 7, 100, 4096):
        pages = list(svc.scan(0.0, 2001.0, page_size))
        got_k = np.concatenate([p.keys[p.live_mask] for p in pages])
        got_v = np.concatenate([p.vals[p.live_mask] for p in pages])
        np.testing.assert_array_equal(got_k, live_k)
        np.testing.assert_array_equal(got_v, live_v)
        counts = [p.count for p in pages]
        assert all(c == page_size for c in counts[:-1])
        assert counts[-1] == live_k.size - page_size * (len(counts) - 1)
    # a range that is an exact multiple of the page size
    got_k, _ = _concat(svc.scan(float(live_k[0]), float(live_k[100]), 50))
    assert got_k.size == 100
    # empty, inverted, and out-of-domain ranges scan nothing
    assert list(svc.scan(10.0, 10.0, 64)) == []
    assert list(svc.scan(500.0, 10.0, 64)) == []
    assert list(svc.scan(1e12, 2e12, 64)) == []
    assert list(svc.scan(-500.0, -1.0, 64)) == []
    with pytest.raises(ValueError):
        next(iter(svc.scan(0.0, 1.0, 0)))


def test_scan_resurrected_keys_carry_staged_values():
    """Tombstone-then-reinsert: the scanned row must carry the staged
    value, not the dead base row's."""
    base = np.arange(10, dtype=np.float64)
    vals = np.arange(10, dtype=np.int64) * 100
    svc = IndexService(base, ServiceConfig(delta_capacity=64), vals=vals)
    svc.delete(np.array([3.0, 4.0]))
    svc.insert(np.array([3.0]), np.array([999]))
    got_k, got_v = _concat(svc.scan(0.0, 10.0, 4))
    want_k = np.array([0.0, 1.0, 2.0, 3.0, 5.0, 6.0, 7.0, 8.0, 9.0])
    want_v = np.array([0, 100, 200, 999, 500, 600, 700, 800, 900])
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)


# --------------------------------------------------------------------------
# device path: kernel vs fallback bit-identity, device vs host
# --------------------------------------------------------------------------

def test_scan_kernel_bit_identical_to_fallback_any_query():
    """Pallas scan-page kernel vs XLA fallback on adversarial inputs:
    pads, ranks past the end, negative starts, empty deltas."""
    rng = np.random.default_rng(0)
    for trial in range(4):
        nb = int(rng.integers(40, 700))
        base = np.sort(rng.choice(
            np.arange(0, 1 << 20, 3, dtype=np.float64), nb, replace=False))
        norm = ((base - base[0]) / (base[-1] - base[0])).astype(np.float32)
        bvals = rng.integers(0, 1 << 30, nb).astype(np.int32)
        ni = int(rng.integers(0, 50))
        pad_i = 64
        ins = np.full(pad_i, np.inf, np.float32)
        ins[:ni] = np.sort(rng.random(ni).astype(np.float32))
        ivals = np.zeros(pad_i, np.int32)
        ivals[:ni] = rng.integers(0, 1 << 30, ni)
        nd = int(rng.integers(0, min(30, nb)))
        dpos = np.full(32, nb, np.int32)
        dpos[:nd] = np.sort(rng.choice(nb, nd, replace=False))
        end = nb - nd + ni
        starts = np.array(
            [-7, 0, 1, end // 2, end - 1, end, end + 99], np.int32
        )
        for page_size in (8, 129):
            a = ops.rmi_scan_page_op(
                starts, norm, bvals, ins, ivals, dpos, end,
                page_size=page_size, use_kernel=True,
            )
            b = ops.rmi_scan_page_op(
                starts, norm, bvals, ins, ivals, dpos, end,
                page_size=page_size, use_kernel=False,
            )
            for x, y in zip(a, b):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_scan_batch_device_matches_host_pages():
    """On a float32-injective lattice the device scan (normalized f32
    keys, int32 vals) must match the exact host pages row for row —
    kernel strategy and XLA strategy alike."""
    base = np.arange(2, 6002, dtype=np.float64) * 1024.0
    vals = np.arange(base.size, dtype=np.int64) * 3
    for strategy in ("binary", "pallas_fused"):
        svc = IndexService(
            base, ServiceConfig(delta_capacity=1024, strategy=strategy),
            vals=vals,
        )
        svc.insert(
            np.arange(3, 1500, 7, dtype=np.float64) * 1024.0 + 512.0,
            np.arange(214, dtype=np.int64) + 10_000,
        )
        svc.delete(base[::13])
        lo, hi = float(base[5]), float(base[-5])
        keys, dvals, live = svc.scan_batch(lo, hi, 128)
        m = np.asarray(live).ravel()
        got_k = np.asarray(keys).ravel()[m]
        got_v = np.asarray(dvals).ravel()[m]
        host_k, host_v = _concat(svc.scan(lo, hi, 128))
        snap = svc._mgr.current()
        np.testing.assert_array_equal(got_k, snap.keys.normalize(host_k))
        np.testing.assert_array_equal(got_v, host_v.astype(np.int32))


def _fused_scan_inputs(op):
    """(op, args, static arguments, reference) for one fused scan op
    over a churned lattice: `rmi_scan_range_op` over the service's scan plane, or
    `rmi_scan_page_op` over its rank-addressed plan."""
    from repro.index_service.scan import device_scan_plan, pin_view
    from repro.kernels import ref

    base = np.arange(2, 3002, dtype=np.float64) * 1024.0
    svc = IndexService(
        base, ServiceConfig(delta_capacity=512),
        vals=np.arange(base.size, dtype=np.int64) * 3,
    )
    svc.insert(np.arange(3, 700, 7, dtype=np.float64) * 1024.0 + 512.0,
               np.arange(100, dtype=np.int64) + 10_000)
    svc.delete(base[::13])
    lo, hi = base[100], base[400]
    snap = svc._mgr.current()
    base_norm, bvals = (np.asarray(a) for a in snap._device_base())
    if op == "range":
        _, slab, _ = svc._scan_plane_cached()
        args = (snap.keys.normalize(np.array([lo, hi])), base_norm, bvals,
                *(np.asarray(a) for a in slab))
        static = dict(page_size=64, max_pages=6)  # the last page: masked
        return ops.rmi_scan_range_op, args, static, (
            ref.rmi_scan_range_reference)
    view = pin_view(snap, svc._frozen, svc._active)
    r0, r1 = (int(r) for r in view.rank(np.array([lo, hi])))
    ins, ivals, dpos = device_scan_plan(view, snap.keys.normalize)
    starts = np.arange(r0, r1 + 64, 64, dtype=np.int32)
    args = (starts, base_norm, bvals, ins, ivals, dpos,
            np.array([r1], np.int32))
    return ops.rmi_scan_page_op, args, dict(page_size=64), (
        ref.rmi_scan_page_reference)


@pytest.mark.parametrize("op", ["range", "page"])
@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("where", ["numpy", "device"])
def test_fused_scan_ops_return_reference_rows_and_bool_mask(
    op, use_kernel, where
):
    """The fused scan ops return the reference's rows as they leave
    it, with the live mask cast to bool inside the one program, whether
    the arguments arrive as NumPy or as device arrays — kernel
    (interpret mode off-TPU) and XLA twin alike."""
    import jax
    import jax.numpy as jnp

    fn, args, static, reference = _fused_scan_inputs(op)
    want = jax.jit(functools.partial(reference, **static))(
        *(jnp.asarray(a) for a in args))
    if where == "device":
        args = tuple(jnp.asarray(a) for a in args)
    got = fn(*args, use_kernel=use_kernel, **static)
    assert got[2].dtype == bool
    assert np.asarray(want[2]).any() and not np.asarray(want[2]).all()
    for g, w in zip(got, (want[0], want[1], want[2].astype(bool))):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _sharded_lattice(k, n=9_000, strategy="binary"):
    """Float32-injective lattice sharded service + live dict oracle."""
    base = np.arange(2, n + 2, dtype=np.float64) * 1024.0
    vals = np.arange(n, dtype=np.int64) * 5
    svc = ShardedIndexService(
        base, ServiceConfig(num_shards=k, delta_capacity=1024,
                            strategy=strategy),
        vals=vals,
    )
    return svc, dict(zip(base.tolist(), vals.tolist()))


def _assert_scan_batch_matches_host(svc, lo, hi, page_size):
    keys, vals, live = svc.scan_batch(lo, hi, page_size)
    m = np.asarray(live).ravel()
    # the stream is dense: live rows form a prefix of the page matrix
    assert (np.cumsum(~m) * m).sum() == 0
    got_k = np.asarray(keys).ravel()[m]
    got_v = np.asarray(vals).ravel()[m]
    host_k, host_v = _concat(svc.scan(lo, hi, page_size))
    np.testing.assert_array_equal(got_k, svc.scan_normalize(host_k))
    np.testing.assert_array_equal(got_v, host_v.astype(np.int32))


@pytest.mark.parametrize("k", KS)
def test_sharded_scan_batch_matches_host_pages(k):
    """One-dispatch sharded device scan vs the host `scan()` page
    stream, bit-for-bit in the plane's frame, through staged inserts,
    tombstones, and per-shard compactions at K in {1, 3, 8}."""
    rng = np.random.default_rng(k + 60)
    svc, live = _sharded_lattice(k)
    base = np.array(sorted(live))
    for round_ in range(3):
        ins = np.unique(rng.integers(2, 2 + base.size, 400)) * 1024.0 + 512.0
        ins = ins[~np.isin(ins, np.array(sorted(live)))]
        svc.insert(ins, np.arange(ins.size, dtype=np.int64) + 10_000)
        live.update(zip(ins.tolist(), (np.arange(ins.size) + 10_000).tolist()))
        arr = np.array(sorted(live))
        dels = rng.choice(arr, 200, replace=False)
        svc.delete(dels)
        for x in dels:
            del live[float(x)]
        arr = np.array(sorted(live))
        lo = float(arr[int(rng.integers(0, arr.size // 2))])
        hi = float(arr[int(rng.integers(arr.size // 2, arr.size))])
        for page_size in (97, 256):
            _assert_scan_batch_matches_host(svc, lo, hi, page_size)
    # empty, inverted, and out-of-domain ranges: fully masked pages
    arr = np.array(sorted(live))
    for lo, hi in ((arr[10], arr[10]), (arr[-5], arr[5]),
                   (arr[-1] + 7.0, arr[-1] + 9.0)):
        _, _, live_m = svc.scan_batch(float(lo), float(hi), 64)
        assert not np.asarray(live_m).any()


def test_sharded_scan_batch_survives_rebalance():
    """scan_batch answers for call-time state across a rebalance: the
    plane cache must rebuild (new shard services, new frame), not serve
    stale slabs."""
    base = np.arange(2, 9_002, dtype=np.float64) * 1024.0
    vals = np.arange(base.size, dtype=np.int64) * 5
    svc = ShardedIndexService(base, ServiceConfig(
        num_shards=4, delta_capacity=4096, shard_balance_factor=1.5,
    ), vals=vals)
    lo, hi = float(base[100]), float(base[-100])
    _assert_scan_batch_matches_host(svc, lo, hi, 128)
    # hot-tail insert: routes everything to the last shard -> rebalance
    # (tail sized so the re-built shared frame keeps the 1024-step
    # lattice float32-injective — the device scan's endpoint caveat)
    hot = base.max() + 1024.0 + np.arange(3_000, dtype=np.float64) * 1024.0
    svc.insert(hot, np.full(hot.size, 7, np.int64))
    assert svc.stats["rebalances"] >= 1
    _assert_scan_batch_matches_host(svc, lo, float(hot[-1]) + 1.0, 128)


def test_sharded_device_results_survive_incremental_rebuild():
    """Results returned BEFORE a write must stay byte-stable after the
    incremental plane rebuild: `jnp.asarray` can zero-copy ALIAS a
    float32 NumPy buffer on the CPU backend, so the plane caches must
    upload COPIES of the mutable host mirrors — an aliased upload
    would rewrite earlier calls' device arrays in place."""
    svc, live = _sharded_lattice(3, n=6_000)
    base = np.array(sorted(live))
    lo, hi = float(base[5]), float(base[-5])
    k1, v1, m1 = svc.scan_batch(lo, hi, 128)
    r1 = svc.lookup_batch(base[::7])
    want = (np.asarray(k1).copy(), np.asarray(v1).copy(),
            np.asarray(m1).copy(), np.asarray(r1).copy())
    svc.insert(np.arange(3, 600, 11, dtype=np.float64) * 1024.0 + 512.0)
    svc.scan_batch(lo, hi, 128)   # incremental rebuilds mutate mirrors
    svc.lookup_batch(base[::7])
    for got, exp in zip((k1, v1, m1, r1), want):
        np.testing.assert_array_equal(np.asarray(got), exp)


def test_sharded_scan_batch_kernel_matches_fallback():
    """Grid kernel vs vmapped XLA fallback through the service: same
    slabs, bit-identical page stream."""
    svc_k, _ = _sharded_lattice(3, n=3_000, strategy="pallas_fused")
    svc_x, _ = _sharded_lattice(3, n=3_000, strategy="binary")
    ins = np.arange(5, 600, 11, dtype=np.float64) * 1024.0 + 512.0
    for svc in (svc_k, svc_x):
        svc.insert(ins, np.arange(ins.size, dtype=np.int64))
        svc.delete(np.arange(2, 3002, 17, dtype=np.float64) * 1024.0)
    lo, hi = 5.0 * 1024.0, 2_900.0 * 1024.0
    a = svc_k.scan_batch(lo, hi, 64)
    b = svc_x.scan_batch(lo, hi, 64)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# KV page table consumer
# --------------------------------------------------------------------------

def test_paged_kv_scan_streams_table_in_merge_order():
    from repro.serve.kvcache import MAX_PAGES_PER_REQ, PagedKVAllocator

    rng = np.random.default_rng(2)
    alloc = PagedKVAllocator(num_pages=2048, page_size=16,
                             delta_capacity=128, num_shards=4)
    active = []
    for uid in range(120):
        alloc.alloc(uid, int(rng.integers(1, 8)) * 16)
        active.append(uid)
    # bootstrap (dict) mode scans before any index exists
    want = sorted(alloc._table.items())
    got_k, got_v = _concat(alloc.scan(0.0, float(1 << 60), 100))
    np.testing.assert_array_equal(got_k, [k for k, _ in want])
    np.testing.assert_array_equal(got_v, [v for _, v in want])
    alloc.rebuild_index()
    # churn so the sharded deltas hold staged inserts AND tombstones
    for uid in rng.choice(active, 40, replace=False):
        alloc.free(int(uid))
        active.remove(uid)
    for uid in range(200, 260):
        alloc.alloc(uid, 32)
        active.append(uid)
    want = sorted(alloc._table.items())
    got_k, got_v = _concat(alloc.scan(0.0, float(1 << 60), 100))
    np.testing.assert_array_equal(got_k, [k for k, _ in want])
    np.testing.assert_array_equal(got_v, [v for _, v in want])
    # per-request walk: physical pages in logical order
    uid = active[-1]
    assert list(alloc.request_pages(uid)) == alloc._per_req[uid]
    lo = uid * MAX_PAGES_PER_REQ
    assert list(alloc.request_pages(uid)) == [
        alloc._table[k] for k in sorted(
            k for k in alloc._table if lo <= k < lo + MAX_PAGES_PER_REQ
        )
    ]


def test_paged_kv_scan_batch_one_dispatch_matches_scan():
    """The device page-table scan: one dispatch, rows identical to the
    host `scan` stream (in the plane's float32 frame), cache reused
    until alloc/free churn bumps a delta version."""
    from repro.kernels import ops as kernels_ops
    from repro.serve.kvcache import PagedKVAllocator

    rng = np.random.default_rng(7)
    alloc = PagedKVAllocator(num_pages=2048, page_size=16,
                             delta_capacity=128, num_shards=4)
    for uid in range(100):
        alloc.alloc(uid, int(rng.integers(1, 6)) * 16)
    alloc.rebuild_index()
    for uid in rng.choice(100, 30, replace=False):
        alloc.free(int(uid))
    for uid in range(200, 240):
        alloc.alloc(uid, 32)
    lo, hi = 0.0, float(1 << 60)
    alloc.scan_batch(lo, hi, 64)  # warm the plane
    with kernels_ops.count_dispatches() as n:
        keys, vals, live = alloc.scan_batch(lo, hi, 64)
        assert n() == 1
    m = np.asarray(live).ravel()
    got_k = np.asarray(keys).ravel()[m]
    got_v = np.asarray(vals).ravel()[m]
    host_k, host_v = _concat(alloc.scan(lo, hi, 64))
    np.testing.assert_array_equal(got_k, alloc.scan_normalize(host_k))
    np.testing.assert_array_equal(got_v, host_v.astype(np.int32))


# --------------------------------------------------------------------------
# staged inserts whose float32 image ties a base key's
# --------------------------------------------------------------------------

def _frame_merge(snap, base, bvals, ins, ivals, lo, hi):
    """The plain sorted merge of live base rows and staged inserts in
    the snapshot's float32 frame: rows whose image lies in [f32(lo),
    f32(hi)), ordered by image, a staged insert before the base keys it
    ties, and by key within each source."""
    keys = np.concatenate([ins, base])
    vals = np.concatenate([ivals, bvals])
    staged = np.concatenate([np.zeros(ins.size), np.ones(base.size)])
    img = snap.keys.normalize(keys)
    order = np.lexsort((keys, staged, img))
    img, vals = img[order], vals[order]
    a, b = snap.keys.normalize(np.array([lo, hi], np.float64))
    m = (img >= a) & (img < b)
    return img[m], vals[m].astype(np.int32)


def _tie_case(case):
    """(base, base values, inserts, insert values, base keys deleted,
    scan ranges) with inserts that tie base keys in float32."""
    if case == "witness":
        base = np.linspace(0, 1000, 20001)
        ins = 1000 + np.array([1e-5, 2e-5, 3e-5])
        return (base, np.arange(base.size), ins,
                np.arange(20001, 20004), np.empty(0),
                [(float(base[-5]), 1001.00003)])
    rng = np.random.default_rng(23)
    # a dense run of keys 1e-6 apart is about 60 keys a float32 image
    # in a frame 1000 wide: at the top (newest-order inserts) and in
    # the middle, staged inserts interleave with it
    at = 1000.0 if case == "top" else 500.0
    run = at - 4e-4 + np.arange(400) * 1e-6
    base = np.unique(np.concatenate([rng.uniform(0, 1000, 4000), run,
                                     [0.0, 1000.0]]))
    ins = np.setdiff1d(np.unique(run[:-1] + rng.uniform(1e-7, 9e-7, 399)
                                 + (4e-4 if case == "top" else 0.0)), base)
    dead = rng.choice(run, 30, replace=False)
    lo, hi = float(run[40]), float(run[-1]) + (8e-4 if case == "top"
                                                else 0.0)
    return (base, np.arange(base.size) * 3, ins,
            10_000 + np.arange(ins.size), dead,
            [(lo, hi), (float(run[0]), float(run[120])),
             (float(run[200]), float(run[200]) + 5e-6)])


@pytest.mark.parametrize("strategy", ["binary", "pallas_fused"])
@pytest.mark.parametrize("case", ["witness", "top", "middle"])
def test_scan_batch_merges_inserts_that_tie_base_keys(case, strategy):
    """A staged insert whose float32 image ties a base key's is merged
    once, and the tied base row once: `scan_batch` equals the plain
    merge in the float32 frame (no row twice, none left out)."""
    base, bvals, ins, ivals, dead, ranges = _tie_case(case)
    svc = IndexService(base, ServiceConfig(strategy=strategy), vals=bvals)
    svc.insert(ins, ivals)
    if dead.size:
        svc.delete(dead)
    snap = svc._mgr.current()
    img = snap.keys.normalize(np.concatenate([base, ins]))
    assert np.unique(img).size < img.size   # the case does tie
    live = ~np.isin(base, dead)
    for lo, hi in ranges:
        keys, vals, mask = (np.asarray(a) for a in
                            svc.scan_batch(lo, hi, 256))
        want_k, want_v = _frame_merge(snap, base[live], bvals[live], ins,
                                      ivals, lo, hi)
        np.testing.assert_array_equal(keys[mask], want_k)
        np.testing.assert_array_equal(vals[mask], want_v)
    if case == "witness":
        got = np.sort(vals[mask])
        np.testing.assert_array_equal(got, np.arange(19996, 20004))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_rank_addressed_scan_pages_merge_inserts_that_tie(use_kernel):
    """The older rank-addressed scan body keeps the same tie rule: its
    pages over every merged rank are the plain merge in the float32
    frame."""
    from repro.index_service.scan import device_scan_plan, pin_view

    base, bvals, ins, ivals, dead, _ = _tie_case("middle")
    svc = IndexService(base, ServiceConfig(), vals=bvals)
    svc.insert(ins, ivals)
    svc.delete(dead)
    snap = svc._mgr.current()
    view = pin_view(snap, svc._frozen, svc._active)
    dins, divals, dpos = device_scan_plan(view, snap.keys.normalize)
    end = view.live_count
    starts = np.arange(0, end, 1024, dtype=np.int32)
    keys, vals, live = (np.asarray(a) for a in ops.rmi_scan_page_op(
        starts, snap.keys.norm, bvals.astype(np.int32), dins, divals, dpos,
        np.array([end], np.int32), page_size=1024, use_kernel=use_kernel))
    live = live.astype(bool)
    alive = ~np.isin(base, dead)
    want_k, want_v = _frame_merge(snap, base[alive], bvals[alive], ins,
                                  ivals, base[0], np.inf)
    np.testing.assert_array_equal(keys[live], want_k)
    np.testing.assert_array_equal(vals[live], want_v)


# --------------------------------------------------------------------------
# the incremental scan plane
# --------------------------------------------------------------------------

def _plane_steps(seed):
    """A seeded sequence of write versions: inserts between them, one
    delete of base keys, one delete of staged keys only, one freeze,
    one compaction; with the plane path each is expected to take: only
    the compaction's new snapshot rebuilds."""
    rng = np.random.default_rng(seed)
    steps = [("insert", "update") for _ in range(int(rng.integers(2, 5)))]
    steps.insert(int(rng.integers(1, len(steps) + 1)),
                 ("delete base", "update"))
    steps += [("delete staged", "update"), ("insert", "update"),
              ("freeze", "update"), ("insert", "update")]
    steps += [("insert", "update")] * int(rng.integers(1, 3))
    steps += [("compact", "rebuild"), ("insert", "update")]
    return steps


@pytest.mark.parametrize("seed", [3, 2**31 + 19, 2**33 + 5])
def test_incremental_scan_plane_matches_a_full_build(seed):
    """After every write version the plane the service serves equals a
    from-scratch packing of the same view bit for bit, and its counters
    count exactly the rebuilds and the updates: every write version of
    one snapshot, tombstones and freezes included, is an update."""
    from repro.index_service.scan import (
        pin_view,
        staged_scan_arrays,
        tombstone_scan_arrays,
    )

    rng = np.random.default_rng(seed)
    base = np.unique(rng.integers(0, 1 << 40, 3000).astype(np.float64))
    svc = IndexService(base, ServiceConfig(delta_capacity=256,
                                           max_delta_levels=2),
                       vals=np.arange(base.size))
    plane, reg = svc._plane, svc.metrics
    staged = []

    def count(name):
        return reg.counter(f"plane.scan.{name}").value

    svc.scan_batch(float(base[10]), float(base[90]), 64)   # the first build
    want = {"rebuilds": 1, "updates": 0}
    for i, (step, path) in enumerate(_plane_steps(seed)):
        if step == "insert":
            fresh = np.setdiff1d(np.unique(
                rng.integers(0, 1 << 40, 20).astype(np.float64)), base)
            svc.insert(fresh, np.arange(fresh.size) + 10_000 * (i + 1))
            staged += list(fresh)
        elif step == "delete base":
            svc.delete(rng.choice(base, 5, replace=False))
        elif step == "delete staged":
            svc.delete(np.array(staged[-3:]))
        elif step == "freeze":
            assert svc.maybe_compact() and svc.num_delta_levels == 1
        else:
            svc.flush()
        want[path + "s"] += 1
        snap, slab, imgs = svc._scan_plane_cached()
        view = pin_view(snap, svc._frozen, svc._active)
        full = staged_scan_arrays(
            view, snap.keys.norm, snap.keys.normalize,
            min_pad=plane.staged_min_pad(view.ins_keys.size),
        ) + tombstone_scan_arrays(
            view.del_pos, min_pad=plane.staged_min_pad(view.del_pos.size))
        for got, ref_arr in zip(slab, full):
            got = np.asarray(got)
            assert got.dtype == ref_arr.dtype and got.shape == ref_arr.shape
            np.testing.assert_array_equal(got, ref_arr)
        np.testing.assert_array_equal(
            imgs, snap.keys.normalize(view.ins_keys))
        assert {k: count(k) for k in want} == want, (i, step)
        # the served plane answers as the exact host scan does
        lo, hi = float(base[200]), float(base[2800])
        keys, _, mask = (np.asarray(a) for a in svc.scan_batch(lo, hi, 256))
        host_k, _ = _concat(svc.scan(lo, hi, 4096))
        np.testing.assert_array_equal(keys[mask],
                                      snap.keys.normalize(host_k))
    upload = count("upload_bytes")
    assert upload > 0 and count("updates") >= 5


def test_non_empty_delta_scans_at_one_pad():
    """Every non-empty delta up to the compaction bound scans through
    one staged-insert pad, so a growing delta compiles no new scan
    program; an empty delta keeps the smallest pad."""
    base = np.arange(2, 4002, dtype=np.float64) * 8.0
    svc = IndexService(base, ServiceConfig(delta_capacity=512),
                       vals=np.arange(base.size))
    pads = set()
    for k in (0, 1, 40, 300, 380):
        if k:
            fresh = np.arange(k, dtype=np.float64) * 8.0 + 3.0 + 8.0 * k
            svc.insert(fresh[~np.isin(fresh, base)])
        _, (ins, *_), _ = svc._scan_plane_cached()
        pads.add((k > 0, ins.shape[0]))
    assert pads == {(False, 64), (True, svc._plane.staged_pad)}
    assert svc._plane.staged_pad >= 2 * 512 + 1


# --------------------------------------------------------------------------
# the rank <-> row map through the tombstones
# --------------------------------------------------------------------------

def _dead_positions(case, rng, n):
    """Sorted dead base positions of one shape of tombstone set."""
    if case == "none":
        return np.empty(0, np.int64)
    if case == "random":
        return np.sort(rng.choice(n, int(rng.integers(1, n // 3)),
                                  replace=False))
    if case == "runs":
        starts = rng.choice(n - 12, 6, replace=False)
        run = [s + np.arange(int(rng.integers(1, 12))) for s in starts]
        return np.unique(np.concatenate(run + [[0, 1, 2]]))
    if case == "ends":
        return np.unique(np.concatenate(
            [[0, n - 1], rng.choice(n, 5, replace=False)]))
    return np.arange(n)   # everything dead


def _live_prefix_rows(t, base, bvals, ins, ivals, ins_rank, dead):
    """NumPy reference of the rank -> row map through the (n+1)-entry
    live-prefix index, ``live_prefix[p] = p - #dead positions < p``:
    the partition ``j = lower_bound(ins_rank, t)`` and the ``(t - j)``-th
    live base row at ``lower_bound(live_prefix, t - j + 1) - 1``, a
    staged insert emitted where its key is at most the base row's."""
    n, ni = base.size, ins.size
    mark = np.zeros(n + 1, np.int64)
    mark[dead + 1] = 1
    live_prefix = np.arange(n + 1) - np.cumsum(mark)
    j = np.searchsorted(ins_rank, t, side="left")
    p = np.searchsorted(live_prefix, t - j + 1, side="left") - 1
    inside = (p >= 0) & (p < n)
    a_key = np.where(inside, base[np.clip(p, 0, n - 1)], np.inf)
    a_val = bvals[np.clip(p, 0, n - 1)]
    c_key = np.where(j < ni, ins[np.clip(j, 0, ni - 1)], np.inf)
    c_val = ivals[np.clip(j, 0, ni - 1)]
    from_ins = c_key <= a_key
    return (np.where(from_ins, c_key, a_key).astype(np.float32),
            np.where(from_ins, c_val, a_val).astype(np.int32),
            live_prefix)


@pytest.mark.parametrize("staged", [0, 9])
@pytest.mark.parametrize("case", ["none", "random", "runs", "ends", "all"])
def test_tombstone_search_maps_ranks_like_the_live_prefix(case, staged):
    """The delta-sized tombstone arrays rank the scan endpoints and
    select each rank's row exactly as the (n+1)-entry live-prefix index
    did, for every rank: tombstone runs, positions 0 and n - 1, every
    base row dead, ranks before the first row and past the last, with
    and without staged inserts; and the live rows are the plain
    merge."""
    import jax

    from repro.index_service.scan import (
        PinnedView,
        staged_scan_arrays,
        tombstone_scan_arrays,
    )
    from repro.kernels import rmi_lookup as rl

    rng = np.random.default_rng(
        ["none", "random", "runs", "ends", "all"].index(case) * 10 + staged)
    n = 200
    # keys 4k and staged 4k + 2 below 4096: exact float32 images, no ties
    base = np.sort(rng.choice(1024, n, replace=False)) * 4.0
    bvals = np.arange(n, dtype=np.int32) * 3
    ins = np.sort(rng.choice(1024, staged, replace=False)) * 4.0 + 2.0
    dead = _dead_positions(case, rng, n)
    view = PinnedView(base_keys=base, base_vals=bvals.astype(np.int64),
                      ins_keys=ins, ins_vals=1000 + np.arange(staged),
                      del_pos=dead.astype(np.int64))

    def normalize(x):
        return (np.asarray(x, np.float64) / 4096.0).astype(np.float32)

    base_norm = normalize(base)
    ins_p, ivals_p, ins_rank = staged_scan_arrays(view, base_norm,
                                                  normalize)
    dead_pos, dead_rank = tombstone_scan_arrays(view.del_pos)
    assert dead_pos.shape == dead_rank.shape and dead_pos.size < 4 * (
        dead.size + 64)
    total = view.live_count
    t = np.arange(-3, total + 6, dtype=np.int32)
    want_k, want_v, live_prefix = _live_prefix_rows(
        t, base_norm, bvals, ins_p, ivals_p, ins_rank, dead)
    valid = (t >= 0) & (t < total)
    want_k = np.where(valid, want_k, np.inf).astype(np.float32)
    want_v = np.where(valid, want_v, 0).astype(np.int32)

    step = rl._search_steps

    @jax.jit
    def device_map(q, t, valid):
        flats = [rl._xla(a) for a in (base_norm, bvals, ins_p, ivals_p,
                                      ins_rank, dead_pos, dead_rank)]
        b, bv, i, iv, ir, dp, dr = flats
        rank = rl._merged_rank(q, b, dp, i, steps=step(n),
                               tsteps=step(dp.size), isteps=step(i.size))
        rows = rl._scan_rows(t, valid, b, bv, i, iv, ir, dr,
                             msteps=step(ir.size), tsteps=step(dr.size))
        return rank, rows

    # endpoints: every stored key, a key's neighbours, before the first
    # key and past the last
    q = normalize(np.concatenate([base, ins, base + 1.0, base - 1.0,
                                  [-8.0, 4095.0, 5000.0]]))
    rank, (keys, vals, live) = (
        jax.tree_util.tree_map(np.asarray, device_map(q, t, valid)))
    want_rank = (live_prefix[np.searchsorted(base_norm, q, side="left")]
                 + np.searchsorted(ins_p[:staged], q, side="left"))
    np.testing.assert_array_equal(rank, want_rank)
    np.testing.assert_array_equal(keys, want_k)
    np.testing.assert_array_equal(vals, want_v)
    np.testing.assert_array_equal(live, valid.astype(np.int32))
    # the reference itself: the live rows are the sorted merge
    alive = np.setdiff1d(np.arange(n), dead)
    merged = np.sort(np.concatenate([base_norm[alive], ins_p[:staged]]))
    np.testing.assert_array_equal(keys[valid], merged)


@pytest.mark.parametrize("plan", ["runs", "ends", "most dead"])
def test_fused_scan_kernel_matches_xla_over_tombstones(plan):
    """The fused scan's Pallas kernel (interpret mode off-TPU) and its
    XLA body return the same rows bit for bit over plans that hold
    tombstones — runs, the first and last base rows, all but a few
    rows dead — for ranges inside, across and past the live rows, and
    both equal the plain merge in the float32 frame."""
    base = np.arange(2, 602, dtype=np.float64) * 1024.0
    rng = np.random.default_rng(["runs", "ends", "most dead"].index(plan))
    dead = {"runs": np.concatenate([base[40:90], base[300:305],
                                    base[::97]]),
            "ends": base[[0, 1, -2, -1]],
            "most dead": np.delete(base, [3, 250, 251, 599])}[plan]
    ins = np.setdiff1d(
        np.unique(rng.integers(2, 602, 40)) * 1024.0 + 512.0, base)
    ranges = [(base[0], base[-1] + 1.0), (base[35], base[95]),
              (base[-3], base[-1] * 2.0), (base[500], base[100])]
    svcs = {}
    for strategy in ("pallas_fused", "binary"):
        svc = IndexService(base, ServiceConfig(delta_capacity=2048,
                                               strategy=strategy),
                           vals=np.arange(base.size) * 7)
        svc.insert(ins, np.arange(ins.size) + 50_000)
        svc.delete(dead)
        svcs[strategy] = svc
    snap = svcs["binary"]._mgr.current()
    alive = ~np.isin(base, dead)
    for lo, hi in ranges:
        a, b = ([np.asarray(x) for x in svcs[s].scan_batch(lo, hi, 64)]
                for s in ("pallas_fused", "binary"))
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        want_k, want_v = _frame_merge(
            snap, base[alive], (np.arange(base.size) * 7)[alive], ins,
            np.arange(ins.size) + 50_000, lo, hi)
        np.testing.assert_array_equal(a[0][a[2]], want_k)
        np.testing.assert_array_equal(a[1][a[2]], want_v)


def _plane_scan_matches_oracle(svc, live, lo, hi, normalize):
    keys, vals, mask = (np.asarray(a) for a in svc.scan_batch(lo, hi, 128))
    want_k, want_v = _oracle_slice(live, lo, hi)
    np.testing.assert_array_equal(keys[mask], normalize(want_k))
    np.testing.assert_array_equal(vals[mask], want_v.astype(np.int32))


def test_deletes_freezes_and_swaps_repack_the_plane_in_delta_size():
    """A delete of base keys, a freeze and a snapshot swap each re-pack
    the scan plane from arrays the size of the delta: the plane counts
    the dead positions it packed, its upload grows by a few KiB, no
    device array of it is sized by the key count, and every scan
    answers as the NumPy merge."""
    n = 20_000
    base = np.arange(2, n + 2, dtype=np.float64) * 1024.0
    svc = IndexService(base, ServiceConfig(delta_capacity=256,
                                           max_delta_levels=2),
                       vals=np.arange(n) * 3)
    live = dict(zip(base.tolist(), (np.arange(n) * 3).tolist()))
    plane, reg = svc._plane, svc.metrics
    lo, hi = float(base[10]), float(base[n - 10])

    def count(name):
        return reg.counter(f"plane.scan.{name}").value

    def step(write, path, dead):
        before = {k: count(k) for k in ("updates", "rebuilds",
                                        "tombstones", "upload_bytes")}
        write()
        snap, slab, _ = svc._scan_plane_cached()
        assert all(a.size < n // 8 for a in slab)
        assert count(path + "s") == before[path + "s"] + 1
        assert count("tombstones") - before["tombstones"] == dead
        sent = count("upload_bytes") - before["upload_bytes"]
        assert sent == sum(a.nbytes for a in slab)
        assert sent <= 20 * plane.staged_pad
        _plane_scan_matches_oracle(svc, live, lo, hi, snap.keys.normalize)

    step(lambda: None, "rebuild", 0)   # the first build
    doomed = np.concatenate([base[:3], base[500:540], base[[n - 1]]])

    def delete():
        svc.delete(doomed)
        for k in doomed.tolist():
            del live[k]
    step(delete, "update", doomed.size)
    fresh = base[:5] + 512.0

    def insert():
        svc.insert(fresh, np.arange(5) + 90_000)
        live.update(zip(fresh.tolist(), (np.arange(5) + 90_000).tolist()))
    step(insert, "update", doomed.size)
    def freeze():
        assert svc.maybe_compact() and svc.num_delta_levels == 1
    step(freeze, "update", doomed.size)
    step(lambda: svc.flush(), "rebuild", 0)


def test_sharded_and_page_table_planes_keep_tombstones_in_delta_rows():
    """The stacked planes of the sharded service (K=3) and of the KV page
    table hold their tombstones in (S, delta pad) rows: a delete or a
    freeze re-uploads only those rows and the staged ones, never the
    base keys, and every scan answers as the NumPy merge."""
    from repro.index_service.scan import SLAB_ROWS
    from repro.serve.kvcache import PagedKVAllocator

    base = np.arange(2, 9_002, dtype=np.float64) * 1024.0
    svc = ShardedIndexService(
        base, ServiceConfig(num_shards=3, delta_capacity=1024,
                            max_delta_levels=2),
        vals=np.arange(base.size) * 5)
    live = dict(zip(base.tolist(), (np.arange(base.size) * 5).tolist()))
    lo, hi = float(base[5]), float(base[-5])
    _plane_scan_matches_oracle(svc, live, lo, hi, svc.scan_normalize)
    first = svc._scan_plane()
    doomed = np.concatenate([base[:2], base[2990:3020], base[[-1]]])
    svc.delete(doomed)
    for k in doomed.tolist():
        del live[k]
    for write in ("delete", "freeze", "swap"):
        if write == "freeze":
            for shard in svc._shards:
                assert shard.maybe_compact()
                assert shard.num_delta_levels == 1
        elif write == "swap":
            svc.flush()
        plane = svc._scan_plane()
        pad = plane.dev["dead_pos"].shape
        assert pad == plane.dev["dead_rank"].shape == plane.dev["ins"].shape
        assert pad[0] == 3 and pad[1] < 9_000 // 8
        dead = (plane.host["dead_pos"] < (1 << 30)).sum()
        assert dead == (doomed.size if write != "swap" else 0)
        if write != "swap":
            assert plane.dev["base"] is first.dev["base"]
        _plane_scan_matches_oracle(svc, live, lo, hi, svc.scan_normalize)

    alloc = PagedKVAllocator(num_pages=2048, page_size=16,
                             delta_capacity=128, num_shards=4)
    for uid in range(120):
        alloc.alloc(uid, 48)
    alloc.rebuild_index()
    for uid in range(0, 120, 3):
        alloc.free(uid)
    slab = dict(zip(SLAB_ROWS, alloc._scan_plane()["slab"]))
    assert slab["dead_pos"].shape[0] == 4
    dead = int((np.asarray(slab["dead_pos"]) < (1 << 30)).sum())
    assert dead == 40 * 3   # three pages of each freed request
    lo, hi = 0.0, float(1 << 60)
    keys, vals, mask = (np.asarray(a)
                        for a in alloc.scan_batch(lo, hi, 64))
    table = sorted(alloc._table.items())
    np.testing.assert_array_equal(
        keys[mask], alloc.scan_normalize(np.array([k for k, _ in table])))
    np.testing.assert_array_equal(vals[mask], [v for _, v in table])
