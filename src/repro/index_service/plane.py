"""Device plane: the device-resident mirrors behind the hot read path.

`IndexService` used to own two ad-hoc cache slots — the merged-lookup
delta slab and the fused-scan plane — inline with its orchestration
(locking, compaction, staging).  The serving tier makes that split
load-bearing: the front-end service loop (`serve.frontend`) must never
touch NumPy mirrors or re-pack logic, only *ask the plane* for the
device arrays matching a consistent (snapshot, frozen, active) capture.
This module is that boundary:

  * orchestration (service.py) decides WHAT state is current — it holds
    the lock, captures the (snapshot, frozen, active) triple, and tells
    the plane when writes or swaps retire state (`drop_*`);
  * the plane decides WHETHER device arrays need re-packing/re-upload
    and owns every jnp buffer — cache checks are identity/version
    comparisons, never data reads, so a hit costs two counter bumps.

Cache coherence keys live here too (`scan_plane_key`): snapshot and
delta-buffer identities plus delta mutation versions, shared by the
unsharded plane and the sharded per-shard slab diff so a new delta
level invalidates every plane consistently.
"""

from __future__ import annotations

import weakref
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.index_service.delta import combine_for_device, iter_levels
from repro.index_service.scan import staged_scan_arrays, tombstone_scan_arrays
from repro.obs import trace as obs_trace

# the smallest pad of the scan plane's delta-sized arrays: an empty
# delta keeps it, so a read-only deployment runs the scan program it
# always ran
EMPTY_STAGED_PAD = 64


def scan_plane_key(snap, frozen, active) -> tuple:
    """THE cache-coherence key for device scan planes: snapshot
    identity plus (identity, mutation version) per delta level —
    ``frozen`` may be None, one buffer, or the leveled compactor's
    oldest-first stack.  Both the unsharded plane cache and the sharded
    per-shard slab diff use this one definition — a new delta level
    added here invalidates every plane consistently."""
    return (snap,) + tuple(
        (lv, lv.version) for lv in iter_levels(frozen, active)
    )


def scan_plane_key_eq(a: tuple, b: tuple) -> bool:
    if len(a) != len(b) or a[0] is not b[0]:
        return False
    return all(
        x[0] is y[0] and x[1] == y[1] for x, y in zip(a[1:], b[1:])
    )


class DevicePlane:
    """Device-resident read-path state for ONE IndexService.

    Two cached surfaces, each with hit/miss counters in the owning
    service's registry (``plane.lookup.*`` / ``plane.scan.*``):

      * the *lookup slab* — the fused delta arrays `combine_for_device`
        packs for the merged-lookup kernel, keyed on snapshot identity
        (writes drop it explicitly via `drop_lookup`, so the key never
        needs to read delta state);
      * the *scan slab* — the staged-insert arrays
        (`staged_scan_arrays`) and the tombstone arrays
        (`tombstone_scan_arrays`) of the one-dispatch scan, keyed on
        `scan_plane_key` (identity + delta versions, so an unchanged
        delta re-uses the upload outright).  Every build packs and
        uploads only arrays the size of the delta, O(delta) on the host:
        no array of the plane is sized by the base.  Counters
        ``plane.scan.updates`` (builds for a new delta version of the
        snapshot the last build served, span ``plane.scan.update``),
        ``.rebuilds`` (builds for a new snapshot, span
        ``scan.pack_slab``), ``.upload_bytes`` and ``.tombstones`` (the
        dead base positions packed).

    Pads: ``staged_pad`` for a non-empty array (the service derives it
    from its compaction bound, so a growing delta compiles no new scan
    program), `EMPTY_STAGED_PAD` for an empty one.

    Locking contract: `lookup_slab` and `cached_scan_slab` are called
    under the service lock (they read/publish one reference);
    `build_scan_slab` runs OUTSIDE the lock on an immutable pinned
    view, so writers and compaction commits never stall behind a
    re-pack — a plane made stale by a concurrent write just misses its
    key check on the next read."""

    # lixlint: thread-shared
    # lixlint: unsynchronized(cache publishes happen under the owning service lock; see locking contract above)

    def __init__(self, metrics, staged_pad: int = EMPTY_STAGED_PAD):
        self._lookup = None  # (snap, dk, dp)
        self._scan = None    # (key, slab, staged images)
        # the snapshot of the last scan build, held weakly: a retired
        # snapshot's device buffers are not kept alive by the plane
        self._scan_snap = None
        self.staged_pad = staged_pad
        self._ctr = {
            k: metrics.counter(f"plane.{k}")
            for k in ("lookup.hit", "lookup.miss", "scan.hit", "scan.miss",
                      "scan.updates", "scan.rebuilds", "scan.upload_bytes",
                      "scan.tombstones")
        }

    # ---- merged-lookup slab ---------------------------------------------
    def lookup_slab(self, snap, frozen, active) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Device (keys, prefix) delta slab for the merged lookup over
        ``snap``; re-packed only when the snapshot changed since the
        last capture (writes invalidate via `drop_lookup`)."""
        cache = self._lookup
        if cache is None or cache[0] is not snap:
            self._ctr["lookup.miss"].add(1)
            dk, dp = combine_for_device(frozen, active, snap.keys.normalize)
            cache = (snap, jnp.asarray(dk), jnp.asarray(dp))
            self._lookup = cache
        else:
            self._ctr["lookup.hit"].add(1)
        return cache[1], cache[2]

    # ---- fused-scan slab -------------------------------------------------
    def cached_scan_slab(self, key: tuple) -> Optional[Tuple[tuple, np.ndarray]]:
        """(slab, staged images) when the cached plane matches ``key``,
        else None (the caller then pins a view and calls
        `build_scan_slab` outside the lock)."""
        plane = self._scan
        if plane is not None and scan_plane_key_eq(plane[0], key):
            self._ctr["scan.hit"].add(1)
            return plane[1], plane[2]
        self._ctr["scan.miss"].add(1)
        return None

    def staged_min_pad(self, count: int) -> int:
        """The least pad of a delta-sized scan array that holds
        ``count`` entries (effective inserts, or tombstones)."""
        return self.staged_pad if count else EMPTY_STAGED_PAD

    def build_scan_slab(self, key: tuple, view, norm, normalize):
        """The scan plane of an immutable pinned view, published under
        ``key`` (whose first entry is the view's snapshot): ``(ins_norm,
        ins_vals, ins_rank, dead_pos, dead_rank)`` packed and uploaded,
        O(delta).  Returns the slab and the staged inserts' float32
        images (host, sorted).  Publishing is one reference write;
        concurrent builders at worst race to publish equivalent
        slabs."""
        snap, k, m = key[0], view.ins_keys.size, view.del_pos.size
        last = self._scan_snap
        update = last is not None and last() is snap
        with obs_trace.span("plane.scan.update" if update
                            else "scan.pack_slab", cat="plane",
                            staged=int(k), dead=int(m)):
            host = staged_scan_arrays(
                view, norm, normalize, min_pad=self.staged_min_pad(k)
            ) + tombstone_scan_arrays(
                view.del_pos, min_pad=self.staged_min_pad(m))
            slab = tuple(jnp.asarray(a) for a in host)
        self._ctr["scan.updates" if update else "scan.rebuilds"].add(1)
        self._ctr["scan.upload_bytes"].add(sum(a.nbytes for a in host))
        self._ctr["scan.tombstones"].add(int(m))
        staged = host[0][:k]
        self._scan = (key, slab, staged)
        self._scan_snap = weakref.ref(snap)
        return slab, staged

    # ---- invalidation ----------------------------------------------------
    def drop_lookup(self) -> None:
        """A write changed the delta: the lookup slab is stale."""
        self._lookup = None

    def drop(self) -> None:
        """A freeze/swap retired snapshot or delta state: drop both
        surfaces (also releases the retired arrays' device buffers)."""
        self._lookup = None
        self._scan = None
