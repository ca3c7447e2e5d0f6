"""Versioned immutable index snapshots + double-buffered atomic swap.

A snapshot is the unit of consistency for the writable index service:
one (RMI tree, sorted base keys, max_window) triple plus the optional
value payload and base Bloom filter, all built together and never
mutated afterwards.  Batched readers grab ``VersionManager.current()``
once per batch; because a swap only replaces the *reference* (atomic
under the GIL) and the previous snapshot is retained as the second
buffer, an in-flight batch keeps consistent arrays even if a
compaction publishes mid-batch.

Snapshots serialize to a single ``.npz`` per version
(``snapshot-000042.npz``), so a restarted service reloads the latest
version and replays only its delta — restart does not retrain.

Exactness note: device lookups run in the float32 normalized frame,
where distinct raw keys may collide.  ``refine_base_rank`` converts the
jitted float32 lower bound into the exact raw-key lower bound with at
most ``max_dup_run`` vectorized advance steps (the longest run of
float32-equal normalized keys, computed at build time) plus an exact
``searchsorted`` fallback for keys absent from the base (which carry no
RMI window guarantee).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as search_lib
from repro.core.bloom import BloomFilter, build_bloom
from repro.core.keys import KeySet, make_keyset
from repro.core.rmi import RMIConfig, RMIndex, build_rmi, refit_rmi, rmi_lookup
from repro.kernels import ops as kernels_ops
from repro.kernels import ref as kernels_ref
from repro.kernels.rmi_lookup import (
    rmi_lookup_pallas,
    rmi_merged_lookup_pallas,
    rmi_sharded_merged_lookup_pallas,
    stage0_flat,
)

# strategies whose compiled closures enter through a pallas_call
KERNEL_STRATEGIES: Tuple[str, ...] = ("pallas", "pallas_fused",
                                      "sharded_fused")

# each kernel strategy's bit-identical XLA twin: where the sticky
# kernel->fallback failover (`kernels.ops.run_with_failover`) reroutes
# a closure whose pallas_call raises
_FALLBACK_STRATEGY = {
    "pallas": "binary",
    "pallas_fused": "xla_fused",
    "sharded_fused": "xla_fused",
}

_SNAP_RE = re.compile(r"snapshot-(\d+)\.npz$")

# The lookup strategy registry: every name a Snapshot (and through it
# IndexService / the KV page table) accepts for base and merged lookups.
#
#   binary / biased / quaternary — §3.4 search variants over the base,
#       lowered through plain XLA; the merged lookup adds a SECOND
#       dispatch for the delta lower bound + prefix gather.
#   pallas      — base search via the fused Pallas RMI kernel; the
#       delta search remains a separate XLA op (two dispatches).
#   pallas_fused — ONE pallas_call runs stage-0 MLP -> leaf FMA ->
#       first probe -> bounded base search -> delta lower bound ->
#       prefix gather without leaving VMEM (interpret mode off-TPU).
#   xla_fused   — identical-signature pure-XLA fallback for
#       pallas_fused: same arithmetic, bit-identical results, no
#       pallas_call.
#   sharded_fused — the key space split into run-aligned sub-shards,
#       each with its own small RMI; ONE pallas_call with the shard
#       axis as a grid dimension runs every per-shard bounded search,
#       then global ranks reassemble by prefix-summed shard offsets
#       (`ops.sharded_reassemble`).  Same (base_lb, merged_rank)
#       signature; the vmapped XLA fallback shares the per-shard body.
#       The parity suite pins all of these to one np.searchsorted
#       oracle.
MERGED_STRATEGIES: Tuple[str, ...] = (
    "binary", "biased", "quaternary", "pallas", "pallas_fused", "xla_fused",
    "sharded_fused",
)

# sub-shard count for the snapshot-level `sharded_fused` strategy (the
# service-level ShardedIndexService shards by its router instead);
# small snapshots fall back to fewer sub-shards so every chunk keeps
# >= 2 distinct float32 keys
SHARDED_FUSED_SUBSHARDS = 4


# device arrays of the snapshot-level sub-shard plan (jit arguments)
_SHARDED_PLAN_ARRAYS = (
    "stage0", "leaf_w", "leaf_b", "err_lo", "err_hi", "keys", "shard_n",
    "shard_m", "shard_ratio", "starts", "base_off",
)


@functools.partial(
    jax.jit, static_argnames=("strategy", "n", "num_leaves", "max_window"))
def _xla_base(q, tree, base_norm, *, strategy, n, num_leaves, max_window):
    return rmi_lookup(tree, base_norm, q, n=n, num_leaves=num_leaves,
                      max_window=max_window, strategy=strategy)


@functools.partial(
    jax.jit, static_argnames=("strategy", "n", "num_leaves", "max_window"))
def _xla_merged(q, dkeys, dprefix, tree, base_norm, *, strategy, n,
                num_leaves, max_window):
    # the scopes name the program's steps in its operations' metadata
    with jax.named_scope("base_search"):
        b = rmi_lookup(tree, base_norm, q, n=n, num_leaves=num_leaves,
                       max_window=max_window, strategy=strategy)
    with jax.named_scope("delta_search"):
        d = search_lib.lower_bound_full(dkeys, q)
    with jax.named_scope("prefix_gather"):
        return b, b + dprefix[d]


@functools.partial(
    jax.jit, static_argnames=("n", "num_leaves", "max_window"))
def _xla_fused_merged(q, s0, leaf_w, leaf_b, err_lo, err_hi, base_norm,
                      dkeys, dprefix, *, n, num_leaves, max_window):
    return kernels_ref.rmi_merged_lookup_reference(
        q, s0, leaf_w, leaf_b, err_lo, err_hi, base_norm, dkeys, dprefix,
        n=n, num_leaves=num_leaves, max_window=max_window,
    )


@functools.partial(
    jax.jit, static_argnames=("hidden", "n", "num_leaves", "max_window"))
def _pallas_merged(q, s0, leaf_w, leaf_b, err_lo, err_hi, base_norm, dkeys,
                   dprefix, *, hidden, n, num_leaves, max_window):
    b = rmi_lookup_pallas(
        q, s0, leaf_w, leaf_b, err_lo, err_hi, base_norm, hidden=hidden,
        n=n, num_leaves=num_leaves, max_window=max_window,
    )
    return b, b + dprefix[search_lib.lower_bound_full(dkeys, q)]


@functools.partial(jax.jit, static_argnames=("hidden", "max_window"))
def _sharded_merged(q, dkeys, dprefix, plan, *, hidden, max_window):
    """Route -> every sub-shard row runs its bounded search in one
    grid-over-shards pallas_call -> prefix-offset reassembly.  The delta
    stays global at snapshot level (one sorted array), so each row
    searches the same broadcast delta and merged offsets == base
    offsets; per-shard deltas enter at the service level
    (ShardedIndexService).  The pallas call is made directly, not
    through the public op: the closure that calls this program is the
    ONE dispatch record per entry."""
    shard = jnp.searchsorted(plan["starts"], q, side="right").astype(
        jnp.int32)
    s = plan["keys"].shape[0]
    lb, ct = rmi_sharded_merged_lookup_pallas(
        jnp.broadcast_to(q, (s, q.shape[0])), plan["stage0"],
        plan["leaf_w"], plan["leaf_b"], plan["err_lo"], plan["err_hi"],
        plan["keys"], jnp.broadcast_to(dkeys, (s, dkeys.shape[0])),
        jnp.broadcast_to(dprefix, (s, dprefix.shape[0])), plan["shard_n"],
        plan["shard_m"], plan["shard_ratio"], hidden=hidden,
        max_window=max_window,
    )
    return kernels_ops.sharded_reassemble(
        lb, ct, shard, plan["base_off"], plan["base_off"]
    )


def validate_strategy(strategy: str) -> str:
    """Fail-fast membership check shared by every strategy consumer."""
    if strategy not in MERGED_STRATEGIES:
        raise ValueError(
            f"unknown lookup strategy {strategy!r}; "
            f"expected one of {MERGED_STRATEGIES}"
        )
    return strategy


def _max_dup_run(norm: np.ndarray) -> int:
    """Longest run of equal float32 normalized keys (>= 1)."""
    if norm.size < 2:
        return 1
    boundaries = np.nonzero(np.diff(norm) > 0)[0]
    edges = np.concatenate([[-1], boundaries, [norm.size - 1]])
    return int(np.max(np.diff(edges)))


@dataclasses.dataclass
class IndexSnapshot:
    """Immutable by convention: nothing mutates a published snapshot;
    compaction builds a successor and swaps the reference."""

    version: int
    keys: KeySet
    index: RMIndex
    vals: Optional[np.ndarray] = None       # payload aligned with keys.raw
    bloom: Optional[BloomFilter] = None     # existence screen over base keys
    max_dup_run: int = 1

    def __post_init__(self):
        self._compiled: Dict[str, Callable] = {}

    @property
    def n(self) -> int:
        return self.keys.n

    # ---- device path -----------------------------------------------------
    def _kernel_closure_args(self):
        """Static (stage0, leaf arrays, hidden) for the kernel paths."""
        idx = self.index
        s0 = stage0_flat(idx.stage0_params)
        arrs = tuple(jnp.asarray(a) for a in
                     (idx.leaf_w, idx.leaf_b, idx.err_lo, idx.err_hi))
        return s0, arrs, tuple(idx.config.stage0_hidden)

    def _sharded_plan(self) -> Dict[str, object]:
        """Lazy sub-shard decomposition for the `sharded_fused` strategy.

        The float32-normalized base array splits into up to
        `SHARDED_FUSED_SUBSHARDS` contiguous chunks whose cut points
        are *run-aligned* (moved to the start of any equal-f32 run), so
        no duplicate run straddles a boundary and the route rule
        ``shard(q) = #{chunk starts <= q}`` keeps the global lower
        bound decomposable as ``chunk_offset + local lower bound`` for
        every query.  Each chunk gets its own linear-stage-0 RMI built
        directly in the global normalized frame (KeySet constructed
        by hand: norm IS the chunk, so stored keys hit the per-shard
        window contract bit-for-bit), and the per-shard arrays stack
        zero/inf-padded with true sizes carried as traced scalars.
        """
        plan = getattr(self, "_shard_plan", None)
        if plan is not None:
            return plan
        norm = self.keys.norm
        n = self.n
        s = max(1, min(SHARDED_FUSED_SUBSHARDS, n // 512))
        while True:
            cuts = sorted(
                {int(np.searchsorted(norm, norm[(j * n) // s], side="left"))
                 for j in range(1, s)} - {0, n}
            )
            bounds = [0] + cuts + [n]
            chunks = [norm[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
            if s == 1 or all(np.unique(c).size >= 2 for c in chunks):
                break
            s -= 1  # a chunk collapsed to one f32 run: coarsen
        s = len(chunks)

        rmis = []
        for chunk in chunks:
            ks = KeySet(raw=chunk.astype(np.float64), norm=chunk,
                        lo=0.0, hi=1.0)
            rmis.append(build_rmi(ks, RMIConfig(
                num_leaves=max(8, chunk.size // 48),
                stage0_hidden=(), stage0_train_steps=0,
            )))
        shard_n = np.array([c.size for c in chunks], np.int32)
        base_off = np.zeros(s, np.int32)
        base_off[1:] = np.cumsum(shard_n[:-1])
        plan = {
            **kernels_ops.stack_shard_arrays(rmis, chunks),
            "S": s,
            "starts": jnp.asarray(np.array(
                [c[0] for c in chunks[1:]], np.float32)),
            "base_off": jnp.asarray(base_off),
        }
        self._shard_plan = plan
        return plan

    def _device_base(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Snapshot-resident device buffers (normalized f32 keys, i32
        payload), uploaded once per snapshot and shared by every
        compiled closure — the snapshot side of the incremental
        device-plane cache (closures used to upload their own copies
        per (strategy, page-size) cache key)."""
        cached = self._compiled.get("devbase")
        if cached is None:
            base_norm = jnp.asarray(self.keys.norm)
            if self.vals is not None:
                bvals = jnp.asarray(np.clip(
                    self.vals, np.iinfo(np.int32).min, np.iinfo(np.int32).max
                ).astype(np.int32))
            else:
                bvals = jnp.zeros((self.n,), jnp.int32)
            cached = self._compiled["devbase"] = (base_norm, bvals)
        return cached

    def merged_lookup_fn(self, strategy: str = "binary") -> Callable:
        """jit fn (q_norm, delta_keys, delta_prefix) -> (base_lb, rank).

        One RMI bounded search over the base plus one fixed-trip
        branchless lower bound over the fused delta array and a single
        prefix gather — as two dispatches (`binary`/`biased`/
        `quaternary`/`pallas`) or one fused kernel (`pallas_fused`,
        with `xla_fused` its bit-identical XLA fallback); see
        MERGED_STRATEGIES.  Retraces per (snapshot size, delta capacity
        bucket) — `combine_for_device` pads the delta to power-of-two
        buckets so individual writes never retrace.  The snapshot's
        device arrays enter the programs as arguments: a closed-over
        array would be baked into the executable as a constant.
        """
        validate_strategy(strategy)
        fn = self._compiled.get(strategy)
        if fn is None:
            base_norm = self._device_base()[0]
            idx = self.index
            static = dict(n=idx.n, num_leaves=idx.num_leaves,
                          max_window=idx.max_window)
            if strategy == "sharded_fused":
                plan = self._sharded_plan()
                arrays = {k: plan[k] for k in _SHARDED_PLAN_ARRAYS}

                def merged(q, dkeys, dprefix):
                    return _sharded_merged(
                        q, dkeys, dprefix, arrays, hidden=plan["hidden"],
                        max_window=plan["max_window"],
                    )
            elif strategy in ("pallas_fused", "xla_fused", "pallas"):
                s0, arrs, hidden = self._kernel_closure_args()
                impl = {"pallas_fused": rmi_merged_lookup_pallas,
                        "xla_fused": _xla_fused_merged,
                        "pallas": _pallas_merged}[strategy]
                if strategy != "xla_fused":
                    static["hidden"] = hidden

                def merged(q, dkeys, dprefix):
                    return impl(q, s0, *arrs, base_norm, dkeys, dprefix,
                                **static)
            else:
                tree = idx.as_pytree()

                def merged(q, dkeys, dprefix):
                    return _xla_merged(q, dkeys, dprefix, tree, base_norm,
                                       strategy=strategy, **static)

            inner = merged
            kernel = strategy in KERNEL_STRATEGIES

            def counted(q, dkeys, dprefix, _inner=inner):
                # ONE device-program entry per call: count it and
                # attribute wall time to (merged_lookup, strategy)
                with kernels_ops.dispatch_span(
                    "merged_lookup", kernel=kernel, strategy=strategy,
                ):
                    return _inner(q, dkeys, dprefix)

            if kernel:
                # kernel closures ride the sticky failover policy onto
                # their bit-identical XLA twin (built lazily, and itself
                # counted under its OWN strategy tag, so attribution
                # shows which program really ran)
                fb = _FALLBACK_STRATEGY[strategy]

                def counted(q, dkeys, dprefix, _k=counted):
                    return kernels_ops.run_with_failover(
                        "merged_lookup", strategy,
                        lambda: _k(q, dkeys, dprefix),
                        lambda: self.merged_lookup_fn(fb)(
                            q, dkeys, dprefix),
                    )

            fn = self._compiled[strategy] = counted
        return fn

    def scan_page_fn(
        self, strategy: str = "binary", page_size: int = 256
    ) -> Callable:
        """jit fn (starts, ins_keys, ins_vals, del_pos, end_rank) ->
        (keys (G, page_size) f32, vals i32, live_mask bool) — one page
        of merged rows per start rank, gathered straight out of
        base+delta merge order without materializing the merge.

        Registered through the same strategy registry as the lookups:
        the kernel strategies (``pallas``/``pallas_fused``/
        ``sharded_fused``) run `rmi_scan_page_pallas` (interpret mode
        off-TPU); everything else lowers to the bit-identical XLA
        fallback (`ref.rmi_scan_page_reference`).  Delta inputs come
        from `scan.device_scan_plan` (power-of-two pad buckets, so the
        jit cache is keyed per bucket).  Same float32/int32 exactness
        caveat as ``lookup_batch`` — the host `IndexService.scan` path
        is the exact float64 surface.
        """
        validate_strategy(strategy)
        use_kernel = strategy in KERNEL_STRATEGIES
        key = f"scan:{'kernel' if use_kernel else 'xla'}:{page_size}"
        fn = self._compiled.get(key)
        if fn is None:
            base_norm, bvals = self._device_base()

            def fn(starts, ins_keys, ins_vals, del_pos, end_rank):
                return kernels_ops.rmi_scan_page_op(
                    starts, base_norm, bvals, ins_keys, ins_vals,
                    del_pos, end_rank,
                    page_size=page_size, use_kernel=use_kernel,
                    strategy=strategy,
                )

            self._compiled[key] = fn
        return fn

    def scan_range_fn(
        self, strategy: str = "binary", page_size: int = 256,
        max_pages: int = 1,
    ) -> Callable:
        """jit fn (bounds, ins_keys, ins_vals, ins_rank, dead_pos,
        dead_rank) -> (keys (max_pages, page_size) f32, vals i32, live_mask bool)
        — the FUSED scan read path: the merged ranks of ``bounds =
        [lo, hi)``, every page start, every row gather and the live
        mask's bool cast all happen inside one device program
        (`kernels.ops.rmi_scan_range_op`: one pallas_call under the
        kernel strategies, the bit-identical XLA program otherwise).
        Nothing ranks on the host;
        ``max_pages`` is only the static output-shape bound (pages past
        the range come back masked).  Delta inputs come from the scan
        plane (`plane.DevicePlane.build_scan_slab`), cached by the
        service per (snapshot, delta version).  Same float32/int32 exactness caveat as
        `lookup_batch` — host `IndexService.scan` is the exact float64
        surface."""
        validate_strategy(strategy)
        use_kernel = strategy in KERNEL_STRATEGIES
        key = f"scanr:{'kernel' if use_kernel else 'xla'}:{page_size}:{max_pages}"
        fn = self._compiled.get(key)
        if fn is None:
            base_norm, bvals = self._device_base()

            def fn(bounds, ins_keys, ins_vals, ins_rank, dead_pos,
                   dead_rank):
                return kernels_ops.rmi_scan_range_op(
                    bounds, base_norm, bvals, ins_keys, ins_vals, ins_rank,
                    dead_pos, dead_rank,
                    page_size=page_size, max_pages=max_pages,
                    use_kernel=use_kernel, strategy=strategy,
                )

            self._compiled[key] = fn
        return fn

    def base_lookup_fn(self, strategy: str = "binary") -> Callable:
        """jit fn (q_norm) -> base lower bound — for callers that
        resolve the delta host-side (e.g. the KV page table) and would
        otherwise pay the fused-delta upload for a discarded result.
        The kernel strategies (`pallas`, `pallas_fused`) both lower to
        the base RMI kernel here (no delta to fuse); `xla_fused` to the
        bit-identical `binary` search."""
        validate_strategy(strategy)
        # pallas/pallas_fused and binary/xla_fused are pairwise the same
        # base computation: share one compiled closure
        alias = {"pallas_fused": "pallas", "xla_fused": "binary"}
        key = f"base:{alias.get(strategy, strategy)}"
        fn = self._compiled.get(key)
        if fn is None:
            base_norm = self._device_base()[0]
            idx = self.index
            static = dict(n=idx.n, num_leaves=idx.num_leaves,
                          max_window=idx.max_window)
            if strategy == "sharded_fused":
                # the sharded base search IS the merged path with
                # nothing staged: reuse its compiled closure with an
                # empty (+inf-padded, zero-prefix) delta
                merged = self.merged_lookup_fn("sharded_fused")
                dk0 = jnp.full((64,), jnp.inf, jnp.float32)
                dp0 = jnp.zeros((65,), jnp.int32)

                def base(q):
                    return merged(q, dk0, dp0)[0]
            elif strategy in ("pallas", "pallas_fused"):
                s0, arrs, hidden = self._kernel_closure_args()

                def base(q):
                    return rmi_lookup_pallas(
                        q, s0, *arrs, base_norm, hidden=hidden, **static
                    )
            else:
                xla_strategy = "binary" if strategy == "xla_fused" else strategy
                tree = idx.as_pytree()

                def base(q):
                    return _xla_base(q, tree, base_norm,
                                     strategy=xla_strategy, **static)

            if strategy != "sharded_fused":
                # sharded_fused delegates to the (already counted)
                # merged closure; everything else is its own program
                # entry — count it here
                inner = base
                tag = alias.get(strategy, strategy)
                kernel = strategy in KERNEL_STRATEGIES

                def base(q, _inner=inner):
                    with kernels_ops.dispatch_span(
                        "base_lookup", kernel=kernel, strategy=tag,
                    ):
                        return _inner(q)

                if kernel:
                    # both kernel aliases lower to the base RMI kernel;
                    # its bit-identical twin is the binary closure
                    def base(q, _k=base):
                        return kernels_ops.run_with_failover(
                            "base_lookup", tag,
                            lambda: _k(q),
                            lambda: self.base_lookup_fn("binary")(q),
                        )

            fn = self._compiled[key] = base
        return fn

    # ---- exact host refinement ------------------------------------------
    def refine_base_rank(
        self, qraw: np.ndarray, b: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """(exact lower bound in base raw keys, present-in-base mask)."""
        raw = self.keys.raw
        n = raw.size
        q = np.asarray(qraw, np.float64)
        i = np.clip(np.asarray(b, np.int64), 0, n)
        # float32 lower bound trails the raw one by at most max_dup_run
        for _ in range(self.max_dup_run):
            c = np.minimum(i, n - 1)
            step = (raw[c] < q) & (i < n)
            if not step.any():
                break
            i = i + step
        in_base = (i < n) & (raw[np.minimum(i, n - 1)] == q)
        miss = ~in_base
        if miss.any():  # absent keys have no window guarantee: exact fallback
            i[miss] = np.searchsorted(raw, q[miss], side="left")
            in_base[miss] = raw[np.minimum(i[miss], n - 1)] == q[miss]
        return i, in_base

    # ---- persistence -----------------------------------------------------
    def save(self, directory: str) -> str:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"snapshot-{self.version:06d}.npz")
        idx = self.index
        cfg = idx.config
        payload = {
            "version": np.int64(self.version),
            "raw": self.keys.raw,
            "key_lo": np.float64(self.keys.lo),
            "key_hi": np.float64(self.keys.hi),
            "max_dup_run": np.int64(self.max_dup_run),
            "leaf_w": idx.leaf_w, "leaf_b": idx.leaf_b,
            "err_lo": idx.err_lo, "err_hi": idx.err_hi, "sigma": idx.sigma,
            "is_btree": idx.is_btree, "seg_lo": idx.seg_lo, "seg_hi": idx.seg_hi,
            "max_window": np.int64(idx.max_window),
            "cfg_num_leaves": np.int64(cfg.num_leaves),
            "cfg_hidden": np.asarray(cfg.stage0_hidden, np.int64),
            "cfg_steps": np.int64(cfg.stage0_train_steps),
            "cfg_sample": np.int64(cfg.stage0_sample or -1),
            "cfg_lr": np.float64(cfg.stage0_lr),
            "cfg_hybrid": np.float64(
                np.nan if cfg.hybrid_threshold is None else cfg.hybrid_threshold
            ),
            "cfg_seed": np.int64(cfg.seed),
        }
        for k, v in idx.stage0_params.items():
            payload[f"s0_{k}"] = v
        if self.vals is not None:
            payload["vals"] = self.vals
        if self.bloom is not None:
            payload["bloom_words"] = self.bloom.words
            payload["bloom_bits"] = np.int64(self.bloom.num_bits)
            payload["bloom_hashes"] = np.int64(self.bloom.num_hashes)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez_compressed(f, **payload)
        os.replace(tmp, path)  # crash-safe publish
        return path

    @staticmethod
    def load(path: str) -> "IndexSnapshot":
        with np.load(path) as z:
            raw = z["raw"]
            lo, hi = float(z["key_lo"]), float(z["key_hi"])
            # build-time normalization (make_keyset / build_snapshot)
            # rejects a degenerate frame outright, so hi > lo for every
            # snapshot we wrote ourselves — but a hand-rolled or
            # corrupted file must not NaN-poison the whole key set
            span = hi - lo
            if span > 0:
                norm = ((raw - lo) / span).astype(np.float32)
            else:
                norm = np.zeros(raw.shape, np.float32)
            keys = KeySet(raw=raw, norm=norm, lo=lo, hi=hi)
            hybrid = float(z["cfg_hybrid"])
            cfg = RMIConfig(
                num_leaves=int(z["cfg_num_leaves"]),
                stage0_hidden=tuple(int(h) for h in z["cfg_hidden"]),
                stage0_train_steps=int(z["cfg_steps"]),
                stage0_sample=(None if int(z["cfg_sample"]) < 0
                               else int(z["cfg_sample"])),
                stage0_lr=float(z["cfg_lr"]),
                hybrid_threshold=None if np.isnan(hybrid) else int(hybrid),
                seed=int(z["cfg_seed"]),
            )
            s0 = {
                k[3:]: z[k] for k in z.files if k.startswith("s0_")
            }
            index = RMIndex(
                config=cfg, n=keys.n, num_leaves=cfg.num_leaves, in_dim=1,
                stage0_params=s0,
                leaf_w=z["leaf_w"], leaf_b=z["leaf_b"],
                err_lo=z["err_lo"], err_hi=z["err_hi"], sigma=z["sigma"],
                is_btree=z["is_btree"], seg_lo=z["seg_lo"], seg_hi=z["seg_hi"],
                max_window=int(z["max_window"]),
            )
            bloom = None
            if "bloom_words" in z.files:
                bloom = BloomFilter(
                    num_bits=int(z["bloom_bits"]),
                    num_hashes=int(z["bloom_hashes"]),
                    words=z["bloom_words"],
                )
            vals = z["vals"] if "vals" in z.files else None
            return IndexSnapshot(
                version=int(z["version"]), keys=keys, index=index,
                vals=vals, bloom=bloom, max_dup_run=int(z["max_dup_run"]),
            )


def build_snapshot(
    raw_keys: np.ndarray,
    *,
    vals: Optional[np.ndarray] = None,
    config: Optional[RMIConfig] = None,
    version: int = 0,
    bloom_fpr: Optional[float] = None,
    warm_from: Optional[IndexSnapshot] = None,
    verbose: bool = False,
) -> Tuple[IndexSnapshot, int]:
    """Build a snapshot over sorted unique raw keys (vals aligned).

    With ``warm_from``, the RMI is rebuilt via `refit_rmi` (stage-0
    reused, only changed leaves refit); falls back to a cold `build_rmi`
    when the warm path is incompatible or the resulting search window
    degrades past 4x the old one.  Returns (snapshot, leaves_refit);
    leaves_refit is -1 for a cold build.
    """
    raw_keys = np.asarray(raw_keys, np.float64)
    if vals is None:
        keys = make_keyset(raw_keys)
    else:
        if raw_keys.size < 2 or raw_keys[0] == raw_keys[-1]:
            raise ValueError("need >= 2 distinct keys")
        lo, hi = float(raw_keys[0]), float(raw_keys[-1])
        norm = ((raw_keys - lo) / (hi - lo)).astype(np.float32)
        keys = KeySet(raw=raw_keys, norm=norm, lo=lo, hi=hi)
    cfg = config or (warm_from.index.config if warm_from else RMIConfig())

    index = None
    refit = -1
    if warm_from is not None:
        try:
            index, refit = refit_rmi(
                warm_from.index, warm_from.keys, keys, config=cfg,
                verbose=verbose,
            )
            if index.max_window > max(4 * warm_from.index.max_window, 64):
                index, refit = None, -1  # fit degraded too far: go cold
        except ValueError:
            index = None
    if index is None:
        index = build_rmi(keys, cfg, verbose=verbose)

    bloom = None
    if bloom_fpr is not None:
        bloom = build_bloom(keys.raw, fpr=bloom_fpr)
    snap = IndexSnapshot(
        version=version, keys=keys, index=index, vals=vals, bloom=bloom,
        max_dup_run=_max_dup_run(keys.norm),
    )
    return snap, refit


class VersionManager:
    """Double-buffered atomic snapshot swap + on-disk version history.

    ``current()`` is a single reference read; publishing retains the
    predecessor (the second buffer) so device arrays backing in-flight
    batches stay alive until the *next* swap.
    """

    def __init__(self, snapshot: IndexSnapshot,
                 directory: Optional[str] = None, keep: int = 2):
        self._lock = threading.Lock()
        self._cur = snapshot
        self._prev: Optional[IndexSnapshot] = None
        self.directory = directory
        self.keep = keep

    @property
    def version(self) -> int:
        return self._cur.version

    def current(self) -> IndexSnapshot:
        return self._cur  # atomic reference read

    def previous(self) -> Optional[IndexSnapshot]:
        return self._prev

    def swap(self, new: IndexSnapshot) -> None:
        with self._lock:
            if new.version <= self._cur.version:
                raise ValueError(
                    f"version must advance: {new.version} <= {self._cur.version}"
                )
            self._prev, self._cur = self._cur, new
        if self.directory is not None:
            self.save_current()

    # ---- persistence -----------------------------------------------------
    def save_current(self) -> str:
        assert self.directory is not None, "VersionManager has no directory"
        path = self._cur.save(self.directory)
        self._gc()
        return path

    def _gc(self) -> None:
        snaps = sorted(
            (f for f in os.listdir(self.directory) if _SNAP_RE.search(f)),
            key=lambda f: int(_SNAP_RE.search(f).group(1)),
        )
        for f in snaps[: -self.keep]:
            os.remove(os.path.join(self.directory, f))

    @staticmethod
    def load_latest(directory: str, keep: int = 2) -> "VersionManager":
        snaps = sorted(
            (f for f in os.listdir(directory) if _SNAP_RE.search(f)),
            key=lambda f: int(_SNAP_RE.search(f).group(1)),
        )
        if not snaps:
            raise FileNotFoundError(f"no snapshots under {directory}")
        snap = IndexSnapshot.load(os.path.join(directory, snaps[-1]))
        return VersionManager(snap, directory=directory, keep=keep)
