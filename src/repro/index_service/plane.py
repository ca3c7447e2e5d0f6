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

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.index_service.delta import combine_for_device, iter_levels
from repro.index_service.scan import device_scan_slab, staged_scan_arrays
from repro.obs import trace as obs_trace

# the smallest staged-insert pad: an empty delta keeps it, so a
# read-only deployment runs the scan program it always ran
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
      * the *scan slab* — staged-insert arrays + the prefix-sum page
        index `device_scan_slab` builds for the one-dispatch scan,
        keyed on `scan_plane_key` (identity + delta versions, so an
        unchanged delta re-uses the upload outright).  Its two parts
        change at different rates: the O(n) page index depends only on
        the snapshot and the tombstone positions, so a write version
        that leaves those alone (an insert) re-packs and uploads only
        the O(delta) staged-insert arrays (``plane.scan.update``) and
        keeps the one device-resident page index; a new snapshot, new
        tombstones or a `drop` rebuild both (``scan.pack_slab``).
        Counters ``plane.scan.updates`` / ``.rebuilds`` /
        ``.upload_bytes`` count the two paths and what they upload.

    Staged-insert pads: ``staged_pad`` for any non-empty delta (the
    service derives it from its compaction bound, so a growing delta
    compiles no new scan program), `EMPTY_STAGED_PAD` for an empty one.

    Locking contract: `lookup_slab` and `cached_scan_slab` are called
    under the service lock (they read/publish one reference); the O(n)
    `build_scan_slab` runs OUTSIDE the lock on an immutable pinned
    view, so writers and compaction commits never stall behind a
    re-pack — a plane made stale by a concurrent write just misses its
    key check on the next read."""

    # lixlint: thread-shared
    # lixlint: unsynchronized(cache publishes happen under the owning service lock; see locking contract above)

    def __init__(self, metrics, staged_pad: int = EMPTY_STAGED_PAD):
        self._lookup = None  # (snap, dk, dp)
        self._scan = None    # (key, slab, staged images)
        self._live = None    # (snap, del_pos, device live_prefix)
        self.staged_pad = staged_pad
        self._ctr = {
            k: metrics.counter(f"plane.{k}")
            for k in ("lookup.hit", "lookup.miss", "scan.hit", "scan.miss",
                      "scan.updates", "scan.rebuilds", "scan.upload_bytes")
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

    def staged_min_pad(self, staged: int) -> int:
        """The least pad of the staged-insert arrays for ``staged``
        effective inserts."""
        return self.staged_pad if staged else EMPTY_STAGED_PAD

    def build_scan_slab(self, key: tuple, view, norm, normalize):
        """The scan plane of an immutable pinned view, published under
        ``key`` (whose first entry is the view's snapshot): the
        staged-insert arrays re-packed and uploaded against the cached
        page index where that was built for the same snapshot and
        tombstone positions, else everything rebuilt.  Returns the
        slab and the staged inserts' float32 images (host, sorted).
        Publishing is one reference write; concurrent builders at worst
        race to publish equivalent slabs."""
        snap, k = key[0], view.ins_keys.size
        pad = self.staged_min_pad(k)
        live = self._live
        if (live is not None and live[0] is snap
                and np.array_equal(live[1], view.del_pos)):
            with obs_trace.span("plane.scan.update", cat="plane",
                                staged=int(k)):
                host = staged_scan_arrays(view, norm, normalize,
                                          min_pad=pad)
                slab = tuple(jnp.asarray(a) for a in host) + (live[2],)
            self._ctr["scan.updates"].add(1)
        else:
            # drop the old page index first: one on the device at a time
            self._scan = self._live = None
            host = device_scan_slab(view, norm, normalize, min_pad=pad)
            slab = tuple(jnp.asarray(a) for a in host)
            self._live = (snap, view.del_pos, slab[3])
            self._ctr["scan.rebuilds"].add(1)
        self._ctr["scan.upload_bytes"].add(sum(a.nbytes for a in host))
        staged = host[0][:k]
        self._scan = (key, slab, staged)
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
        self._live = None
