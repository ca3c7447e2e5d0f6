"""jit'd public wrappers around the Pallas kernels.

Each op picks the kernel when it applies (shape/platform) and falls
back to the pure-jnp reference otherwise; callers never touch
pallas_call directly.  Every op takes ``interpret=None``: Mosaic on a
TPU, Pallas interpret mode on any other backend
(`repro.kernels.resolve_interpret`).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax._src import compiler as _jax_compiler

from repro import faults
from repro.kernels import ref
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.kernels.bloom_probe import bloom_probe_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hash_probe import hash_probe_pallas
from repro.kernels.rmi_lookup import (
    _merged_rank_from_prefix,
    _search_steps,
    _xla,
    rmi_lookup_pallas,
    rmi_merged_lookup_pallas,
    rmi_scan_page_pallas,
    rmi_scan_range_pallas,
    rmi_sharded_merged_lookup_pallas,
    rmi_sharded_scan_page_pallas,
    stage0_flat,
)

# ---------------------------------------------------------------------------
# dispatch accounting & cost attribution
# ---------------------------------------------------------------------------
# Every public RMI op below is one host->device program entry: a single
# jitted XLA executable (which may embed a pallas_call).  Recording
# here — at the non-jitted op boundary, so compiled re-executions still
# count — gives the dispatch-discipline regression tests an observable
# (a read path that silently regresses into per-shard or per-page
# dispatch loops shows up as >1 per logical call) AND the cost model
# its raw material: per-op wall time tagged kernel-vs-fallback and
# strategy, plus the executables compiled inside it.
#
# Counters are per-thread (`count_dispatches()` reads only the calling
# thread's count, so the background compaction thread can never pollute
# a test's window) with a thread-tagged global ledger alongside.
#
# Retraces are real compiles: JAX reports each executable it builds or
# loads from the persistent cache on the compiling thread, and
# `obs.metrics.count_compiles` charges it to the innermost dispatch
# span open there.  A call that hits jax's in-memory caches compiles
# nothing, before or after `reset_dispatch_stats()`.

DISPATCH_COUNT = 0  # process-wide total, kept for back-compat reading


class _DispatchTls(threading.local):
    def __init__(self):
        self.count = 0


_TLS = _DispatchTls()
_DISPATCH_LOCK = threading.Lock()
_THREAD_COUNTS = {}      # thread name -> dispatches recorded on it
_ATTRIBUTION = {}        # (op, path, strategy) -> [count, wall_s, retraces]


@functools.lru_cache(maxsize=None)
def _op_metrics(op: str, path: str):
    reg = obs_metrics.default_registry()
    return (
        reg.counter(f"dispatch.{op}.{path}.count"),
        reg.histogram(f"dispatch.{op}.wall_s"),
        reg.counter(f"dispatch.{op}.retraces"),
    )


def _record_dispatch(op, path, strategy, seconds, compiles) -> None:
    global DISPATCH_COUNT
    _TLS.count += 1
    key = (op, path, strategy or "")
    with _DISPATCH_LOCK:
        DISPATCH_COUNT += 1
        name = threading.current_thread().name
        _THREAD_COUNTS[name] = _THREAD_COUNTS.get(name, 0) + 1
        row = _ATTRIBUTION.get(key)
        if row is None:
            row = _ATTRIBUTION[key] = [0, 0.0, 0]
        row[0] += 1
        row[1] += seconds
        row[2] += compiles
    counter, hist, retraces = _op_metrics(op, path)
    counter.add(1)
    hist.observe(seconds)
    if compiles:
        retraces.add(compiles)


@contextlib.contextmanager
def dispatch_span(op: str, *, kernel: bool, strategy=None):
    """Wrap ONE device-program entry: counts it (per-thread + global),
    attributes its wall time to (op, kernel|fallback, strategy), counts
    the executables compiled inside it as retraces, and emits a trace
    span."""
    path = "kernel" if kernel else "fallback"
    t0 = time.perf_counter()
    with obs_trace.span(f"dispatch.{op}", cat="dispatch", path=path,
                        strategy=strategy or ""), \
            obs_metrics.count_compiles() as compiles:
        try:
            yield
        finally:
            _record_dispatch(op, path, strategy,
                             time.perf_counter() - t0, compiles[0])


@contextlib.contextmanager
def count_dispatches():
    """Context manager yielding a zero-arg callable that reports how
    many device-op entries ran since the context opened — on THIS
    thread only, so concurrent background compaction can't pollute the
    window.  (Back-compat shim over the per-thread counters.)"""
    start = _TLS.count
    yield lambda: _TLS.count - start


def thread_dispatch_counts() -> dict:
    """{thread name: dispatches recorded on it} since the last reset."""
    with _DISPATCH_LOCK:
        return dict(_THREAD_COUNTS)


def dispatch_summary() -> dict:
    """Cost-attribution snapshot: total, per-thread counts, and one row
    per (op, path, strategy) with count / wall seconds / retraces."""
    with _DISPATCH_LOCK:
        total = DISPATCH_COUNT
        by_thread = dict(_THREAD_COUNTS)
        rows = [
            {"op": op, "path": path, "strategy": strategy,
             "count": c, "wall_s": s, "retraces": r}
            for (op, path, strategy), (c, s, r) in sorted(
                _ATTRIBUTION.items())
        ]
    return {"total": total, "by_thread": by_thread, "rows": rows}


def reset_dispatch_stats() -> None:
    """Zero the global ledger (per-thread deltas via `count_dispatches`
    are unaffected)."""
    global DISPATCH_COUNT
    with _DISPATCH_LOCK:
        DISPATCH_COUNT = 0
        _THREAD_COUNTS.clear()
        _ATTRIBUTION.clear()


# ---------------------------------------------------------------------------
# kernel -> fallback strategy failover
# ---------------------------------------------------------------------------
# Every Pallas op below has a bit-identical XLA fallback one branch
# away; a kernel that RAISES WHILE IT RUNS (a device or runtime error,
# an injected ``kernel.dispatch`` fault) must not take the read path
# down with it.  Errors raised while the kernel is traced, lowered or
# compiled are deterministic — a retry cannot heal them, and rerouting
# would hide that the kernel never ran — so they propagate
# (`_is_build_error`).  Policy for run-time errors, per (op, strategy):
#
#   * a healthy kernel that raises is retried ONCE (transient faults
#     heal invisibly), and a second failure stickily reroutes the pair
#     to the fallback — counted as ``kernel_failover``;
#   * while rerouted, every `FAILOVER_REPROBE_EVERY`-th call re-probes
#     the kernel with a single attempt; success re-enables it
#     (``kernel_failover.recoveries``), failure stays on the fallback.
#
# The healthy fast path costs one dict read and one attribute check —
# nothing the dispatch-count or parity suites can observe.

FAILOVER_REPROBE_EVERY = 64


class _Failover:
    """Sticky health record for one (op, strategy) kernel pair."""

    __slots__ = ("lock", "disabled", "since")

    def __init__(self):
        self.lock = threading.Lock()
        self.disabled = False   # reroute every call to the fallback
        self.since = 0          # fallback calls since disablement


_FAILOVER: dict = {}            # (op, strategy) -> _Failover
_FAILOVER_LOCK = threading.Lock()


def _failover_state(op: str, strategy) -> _Failover:
    key = (op, strategy or "")
    st = _FAILOVER.get(key)     # lock-free fast path (GIL-atomic read)
    if st is None:
        with _FAILOVER_LOCK:
            st = _FAILOVER.setdefault(key, _Failover())
    return st


def failover_summary() -> dict:
    """{"op:strategy": {"disabled": bool, "fallback_calls": int}} for
    every kernel pair that has been exercised."""
    with _FAILOVER_LOCK:
        items = list(_FAILOVER.items())
    return {
        f"{op}:{strategy}": {
            "disabled": st.disabled, "fallback_calls": st.since,
        }
        for (op, strategy), st in items
    }


def reset_failover() -> None:
    """Forget all sticky reroutes (tests / bench isolation)."""
    with _FAILOVER_LOCK:
        _FAILOVER.clear()


def _tag_compile_error(err):
    """XLA compile-error hook: mark the error so the failover can tell
    a compile failure from a run-time one (both are JaxRuntimeError)."""
    err.lix_compile_error = True
    return None  # keep the original error


_jax_compiler.register_xla_runtime_error_handler(_tag_compile_error)


def _is_build_error(err: Exception) -> bool:
    """True for errors from tracing, lowering or compiling a kernel:
    Python exceptions other than RuntimeError (ValueError, TypeError,
    Mosaic and lowering errors), NotImplementedError (an unsupported
    lowering), and compile errors tagged by `_tag_compile_error`.
    Run-time errors — JaxRuntimeError from an execution, the injected
    fault — are RuntimeErrors and fail over."""
    return (
        not isinstance(err, RuntimeError)
        or isinstance(err, NotImplementedError)
        or getattr(err, "lix_compile_error", False)
    )


def run_with_failover(op: str, strategy, kernel_fn, fallback_fn):
    """Run ``kernel_fn`` under the retry-once + sticky-failover policy,
    rerouting to ``fallback_fn`` (bit-identical results) when the
    kernel fails while it runs.  A kernel that cannot be built raises
    here instead (`_is_build_error`).  Both callables own their
    dispatch_span, so attribution stays honest about which program
    actually ran.  Fallback errors propagate — with the kernel already
    out of the picture there is nothing left to fail over to."""
    st = _failover_state(op, strategy)
    probe = False
    if st.disabled:
        with st.lock:
            if st.disabled:
                st.since += 1
                if st.since % FAILOVER_REPROBE_EVERY:
                    return fallback_fn()
                probe = True
    reg = obs_metrics.default_registry()
    for _attempt in range(1 if probe else 2):
        try:
            faults.maybe("kernel.dispatch")
            out = kernel_fn()
        except Exception as e:
            if _is_build_error(e):
                raise
            reg.counter("kernel_failover.errors").add(1)
            obs_trace.instant(
                "kernel.error", cat="fault", op=op,
                strategy=strategy or "", error=type(e).__name__,
            )
            continue
        if st.disabled:
            with st.lock:
                st.disabled = False
                st.since = 0
            reg.counter("kernel_failover.recoveries").add(1)
            obs_trace.instant("kernel.recovered", cat="fault", op=op,
                              strategy=strategy or "")
        return out
    if not st.disabled:
        with st.lock:
            st.disabled = True
            st.since = 0
        reg.counter("kernel_failover").add(1)
        obs_trace.instant("kernel.failover", cat="fault", op=op,
                          strategy=strategy or "")
    return fallback_fn()


def rmi_lookup_op(index, sorted_keys_norm, q_norm, *, block_q=1024,
                  interpret=None):
    """Batched RMI lookup via the fused kernel.  `index` is an RMIndex.
    ``interpret=None`` auto-selects interpret mode off-TPU."""
    with dispatch_span("rmi_lookup", kernel=True, strategy="pallas"):
        return rmi_lookup_pallas(
            jnp.asarray(q_norm),
            stage0_flat(index.stage0_params),
            jnp.asarray(index.leaf_w),
            jnp.asarray(index.leaf_b),
            jnp.asarray(index.err_lo),
            jnp.asarray(index.err_hi),
            jnp.asarray(sorted_keys_norm),
            hidden=tuple(index.config.stage0_hidden),
            n=index.n,
            num_leaves=index.num_leaves,
            max_window=index.max_window,
            block_q=block_q,
            interpret=interpret,
        )


def rmi_merged_lookup_op(index, sorted_keys_norm, q_norm, delta_keys,
                         delta_prefix, *, block_q=1024, interpret=None,
                         use_kernel=True, strategy=None):
    """Fused base+delta merged lookup -> (base_lb, merged_rank).

    One kernel dispatch covering the RMI bounded search over the base
    *and* the delta prefix search (`strategy="pallas_fused"`); with
    ``use_kernel=False`` the identical-signature XLA fallback runs
    instead (`strategy="xla_fused"`) — same arithmetic, same results,
    no pallas_call.  A kernel that raises rides the retry-once +
    sticky-failover policy onto that fallback (`run_with_failover`).
    """
    args = (
        jnp.asarray(q_norm),
        stage0_flat(index.stage0_params),
        jnp.asarray(index.leaf_w),
        jnp.asarray(index.leaf_b),
        jnp.asarray(index.err_lo),
        jnp.asarray(index.err_hi),
        jnp.asarray(sorted_keys_norm),
        jnp.asarray(delta_keys),
        jnp.asarray(delta_prefix),
    )

    def run_fallback():
        with dispatch_span(
            "rmi_merged_lookup", kernel=False,
            strategy=(strategy or "xla_fused") if not use_kernel
            else "xla_fused",
        ):
            return ref.rmi_merged_lookup_reference(
                *args, n=index.n, num_leaves=index.num_leaves,
                max_window=index.max_window,
            )

    if not use_kernel:
        return run_fallback()

    def run_kernel():
        with dispatch_span(
            "rmi_merged_lookup", kernel=True,
            strategy=strategy or "pallas_fused",
        ):
            return rmi_merged_lookup_pallas(
                *args,
                hidden=tuple(index.config.stage0_hidden),
                n=index.n,
                num_leaves=index.num_leaves,
                max_window=index.max_window,
                block_q=block_q,
                interpret=interpret,
            )

    return run_with_failover(
        "rmi_merged_lookup", strategy or "pallas_fused",
        run_kernel, run_fallback,
    )


def stack_shard_arrays(indexes, key_arrays):
    """Pad/stack per-shard (RMIndex, sorted f32 keys) pairs into the
    (S, ...) layout `rmi_sharded_merged_lookup_op` consumes — THE one
    place that owns the stacked-layout contract (pad values, dtypes,
    traced-size metadata) for both the snapshot-level sub-shard plan
    and the sharded service's device plan.

    Leaf arrays zero-pad to the widest shard, keys +inf-pad (never
    read: the kernel clips by the traced true size), and
    ``shard_ratio`` is ``float32(m / n)`` computed HOST-side so leaf
    selection stays bit-identical to each shard's build-time
    arithmetic.  Returns a dict of stacked jnp arrays plus the shared
    static ``hidden`` / ``max_window`` entries.
    """
    n_max = max(k.size for k in key_arrays)
    m_max = max(ix.num_leaves for ix in indexes)
    hiddens = {tuple(ix.config.stage0_hidden) for ix in indexes}
    if len(hiddens) != 1:
        raise ValueError("shards disagree on stage-0 architecture")
    nl = len(next(iter(hiddens))) + 1

    def pad_m(a, m):
        return np.pad(np.asarray(a, np.float32), (0, m_max - m))

    stage0 = tuple(
        np.stack([
            np.asarray(ix.stage0_params[f"{kind}{i}"], np.float32)
            for ix in indexes
        ])
        for i in range(nl) for kind in ("w", "b")
    )
    keys = np.stack([
        np.pad(np.asarray(k, np.float32), (0, n_max - k.size),
               constant_values=np.inf)
        for k in key_arrays
    ])
    return {
        "stage0": tuple(jnp.asarray(p) for p in stage0),
        "leaf_w": jnp.asarray(np.stack(
            [pad_m(ix.leaf_w, ix.num_leaves) for ix in indexes])),
        "leaf_b": jnp.asarray(np.stack(
            [pad_m(ix.leaf_b, ix.num_leaves) for ix in indexes])),
        "err_lo": jnp.asarray(np.stack(
            [pad_m(ix.err_lo, ix.num_leaves) for ix in indexes])),
        "err_hi": jnp.asarray(np.stack(
            [pad_m(ix.err_hi, ix.num_leaves) for ix in indexes])),
        "keys": jnp.asarray(keys),
        "shard_n": jnp.asarray(np.array(
            [ix.n for ix in indexes], np.int32)),
        "shard_m": jnp.asarray(np.array(
            [ix.num_leaves for ix in indexes], np.int32)),
        "shard_ratio": jnp.asarray(np.array(
            [np.float32(ix.num_leaves / ix.n) for ix in indexes],
            np.float32)),
        "hidden": next(iter(hiddens)),
        "max_window": max(ix.max_window for ix in indexes),
    }


def pad_shard_row(index, keys_norm, n_pad: int, m_pad: int) -> dict:
    """One shard's row of the stacked lookup layout, padded to an
    explicit ``(n_pad, m_pad)`` bucket — the incremental counterpart of
    `stack_shard_arrays`: the sharded service re-packs only the rows
    whose snapshot changed and keeps the rest byte-stable, so the
    per-shard pad contract must be reproducible row by row.  Same pad
    values as the full stacker (leaf arrays zero, keys +inf, ratio
    host-computed float32(m / n))."""
    k = np.asarray(keys_norm, np.float32)
    m = index.num_leaves

    def pad_m(a):
        return np.pad(np.asarray(a, np.float32), (0, m_pad - m))

    keys = np.full(n_pad, np.inf, np.float32)
    keys[: k.size] = k
    nl = len(index.config.stage0_hidden) + 1
    stage0 = tuple(
        np.asarray(index.stage0_params[f"{kind}{i}"], np.float32)
        for i in range(nl) for kind in ("w", "b")
    )
    return {
        "stage0": stage0,
        "leaf_w": pad_m(index.leaf_w), "leaf_b": pad_m(index.leaf_b),
        "err_lo": pad_m(index.err_lo), "err_hi": pad_m(index.err_hi),
        "keys": keys,
        "n": np.int32(index.n), "m": np.int32(m),
        "ratio": np.float32(index.num_leaves / index.n),
        "max_window": index.max_window,
        "hidden": tuple(index.config.stage0_hidden),
    }


def rmi_sharded_merged_lookup_op(
    q_stacked, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys,
    delta_keys, delta_prefix, shard_n, shard_m, shard_ratio, *,
    hidden=(), max_window, block_q=1024, interpret=None, use_kernel=True,
    strategy=None,
):
    """Per-shard merged lookup over stacked (S, ...) shard arrays.

    One pallas_call with the shard axis as a grid dimension
    (``use_kernel=True``) or the vmapped XLA fallback sharing the same
    per-shard body (``use_kernel=False`` — the path that partitions
    over devices when the stacked arrays carry a shard-axis sharding).
    Returns the per-shard local ``(base_lb, delta_contrib)`` matrices;
    feed them to `sharded_reassemble` for global ranks.  The kernel
    path rides the retry-once + sticky-failover policy onto the vmapped
    fallback.
    """
    args = (
        jnp.asarray(q_stacked),
        tuple(jnp.asarray(p) for p in stage0),
        jnp.asarray(leaf_w), jnp.asarray(leaf_b),
        jnp.asarray(err_lo), jnp.asarray(err_hi),
        jnp.asarray(sorted_keys),
        jnp.asarray(delta_keys), jnp.asarray(delta_prefix),
        jnp.asarray(shard_n), jnp.asarray(shard_m),
        jnp.asarray(shard_ratio),
    )

    def run_fallback():
        with dispatch_span(
            "rmi_sharded_merged_lookup", kernel=False,
            strategy=strategy or "sharded_fused",
        ):
            return _sharded_reference_jit(*args, max_window=max_window)

    if not use_kernel:
        return run_fallback()

    def run_kernel():
        with dispatch_span(
            "rmi_sharded_merged_lookup", kernel=True,
            strategy=strategy or "sharded_fused",
        ):
            return rmi_sharded_merged_lookup_pallas(
                *args, hidden=tuple(hidden), max_window=max_window,
                block_q=block_q, interpret=interpret,
            )

    return run_with_failover(
        "rmi_sharded_merged_lookup", strategy or "sharded_fused",
        run_kernel, run_fallback,
    )


@functools.partial(jax.jit, static_argnames=("max_window",))
def _sharded_reference_jit(q, stage0, leaf_w, leaf_b, err_lo, err_hi,
                           sorted_keys, delta_keys, delta_prefix,
                           shard_n, shard_m, shard_ratio, *, max_window):
    if q.shape[1] == 0:
        empty = jnp.zeros(q.shape, jnp.int32)
        return empty, empty
    return ref.rmi_sharded_merged_lookup_reference(
        q, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys,
        delta_keys, delta_prefix, shard_n, shard_m, shard_ratio,
        max_window=max_window,
    )


@jax.jit
def sharded_reassemble(local_base, delta_contrib, shard_of_q,
                       base_offsets, merged_offsets):
    """Global rank reassembly: pick each query's routed shard row and
    add the prefix-sum offsets.

    ``base_offsets[j]`` is the number of base keys in shards < j and
    ``merged_offsets[j]`` the number of LIVE keys (base + delta net) in
    shards < j, so

        base(q)   = base_offsets[route(q)]   + local_base
        merged(q) = merged_offsets[route(q)] + local_base + delta_contrib

    — the invariant that makes K shards answer with the single global
    array's ranks.  (At the snapshot level, where the delta is global
    rather than per-shard, callers pass ``merged_offsets=base_offsets``.)
    """
    j = shard_of_q.astype(jnp.int32)[None, :]
    lb = jnp.take_along_axis(local_base, j, axis=0)[0]
    ct = jnp.take_along_axis(delta_contrib, j, axis=0)[0]
    jq = j[0]
    return base_offsets[jq] + lb, merged_offsets[jq] + lb + ct


def rmi_scan_page_op(
    starts, base_keys, base_vals, ins_keys, ins_vals, del_pos, end_rank,
    *, page_size=256, use_kernel=True, interpret=None, strategy=None,
):
    """Rank-addressed merged scan gather -> (keys, vals, live_mask).

    Page g streams the merged rows at ranks ``starts[g] + [0,
    page_size)`` of (base minus dead positions) ∪ (effective staged
    inserts) — tombstones elided, insert values woven in — without
    materializing the merge (`strategy` kernel paths); with
    ``use_kernel=False`` the identical-signature XLA fallback runs the
    same `_scan_page_body`, bit-identical for every input.  Keys come
    back in the snapshot's normalized float32 frame and values as
    int32 — the host `index_service.scan` path is the exact float64
    surface; this op is its device data plane.  ``live_mask`` is True
    for rows below ``end_rank`` (partial last page, empty ranges).
    One device program per call, argument casts and the bool mask
    included (`_scan_page_jit`); inputs may be host or device arrays.
    """
    args = (starts, base_keys, base_vals, ins_keys, ins_vals, del_pos,
            end_rank)

    def run_fallback():
        with dispatch_span("rmi_scan_page", kernel=False, strategy=strategy):
            return _scan_page_jit(*args, page_size=page_size,
                                  use_kernel=False, interpret=interpret)

    if not use_kernel:
        return run_fallback()

    def run_kernel():
        with dispatch_span("rmi_scan_page", kernel=True, strategy=strategy):
            return _scan_page_jit(*args, page_size=page_size,
                                  use_kernel=True, interpret=interpret)

    return run_with_failover(
        "rmi_scan_page", strategy, run_kernel, run_fallback,
    )


@functools.partial(
    jax.jit, static_argnames=("page_size", "use_kernel", "interpret")
)
def _scan_page_jit(
    starts, base_keys, base_vals, ins_keys, ins_vals, del_pos, end_rank,
    *, page_size, use_kernel, interpret,
):
    # the dtype contract lives in the trace: a cast to the dtype an
    # argument already has is no operation
    args = (
        starts.astype(jnp.int32), base_keys.astype(jnp.float32),
        base_vals.astype(jnp.int32), ins_keys.astype(jnp.float32),
        ins_vals.astype(jnp.int32), del_pos.astype(jnp.int32),
        jnp.reshape(end_rank, (1,)).astype(jnp.int32),
    )
    if use_kernel:
        keys, vals, live = rmi_scan_page_pallas(
            *args, page_size=page_size, interpret=interpret
        )
    elif starts.shape[0] == 0:
        keys = jnp.zeros((0, page_size), jnp.float32)
        vals = live = jnp.zeros((0, page_size), jnp.int32)
    else:
        keys, vals, live = ref.rmi_scan_page_reference(
            *args, page_size=page_size
        )
    return keys, vals, live.astype(bool)


def rmi_scan_range_op(
    bounds, base_keys, base_vals, live_prefix, ins_keys, ins_vals,
    ins_rank, *, page_size=256, max_pages=1, use_kernel=True,
    interpret=None, strategy=None,
):
    """Fused endpoint-ranking + paged merged-scan gather: ONE device
    program computes the merged ranks of ``bounds = [lo, hi)``, streams
    every page of rows in between and casts the live mask ->
    (keys f32, vals i32, live_mask bool).

    The successor to `rmi_scan_page_op` for the service scan path: no
    host rank feeds the program — ranks, page starts, and rows all
    resolve on device through the prefix-sum page index
    (``live_prefix``, ``ins_rank``, precomputed per (snapshot, delta)
    version by `index_service.scan.device_scan_slab`).  ``max_pages``
    is a conservative *shape* bound (base window + staged inserts);
    pages past the true range come back fully masked.  Kernel and XLA
    fallback share the same body — bit-identical for every input.
    Inputs may be host or device arrays: the dtype casts run inside
    the program (`_scan_range_jit`), so a host ``bounds`` array is
    uploaded by the call itself.
    """
    args = (bounds, base_keys, base_vals, live_prefix, ins_keys, ins_vals,
            ins_rank)

    def run_fallback():
        with dispatch_span("rmi_scan_range", kernel=False, strategy=strategy):
            return _scan_range_jit(
                *args, page_size=page_size, max_pages=max_pages,
                use_kernel=False, interpret=interpret,
            )

    if not use_kernel:
        return run_fallback()

    def run_kernel():
        with dispatch_span("rmi_scan_range", kernel=True, strategy=strategy):
            return _scan_range_jit(
                *args, page_size=page_size, max_pages=max_pages,
                use_kernel=True, interpret=interpret,
            )

    return run_with_failover(
        "rmi_scan_range", strategy, run_kernel, run_fallback,
    )


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "max_pages", "use_kernel", "interpret"),
)
def _scan_range_jit(
    bounds, base_keys, base_vals, live_prefix, ins_keys, ins_vals,
    ins_rank, *, page_size, max_pages, use_kernel, interpret,
):
    # the dtype contract lives in the trace: a cast to the dtype an
    # argument already has is no operation
    args = (
        bounds.astype(jnp.float32), base_keys.astype(jnp.float32),
        base_vals.astype(jnp.int32), live_prefix.astype(jnp.int32),
        ins_keys.astype(jnp.float32), ins_vals.astype(jnp.int32),
        ins_rank.astype(jnp.int32),
    )
    if use_kernel:
        keys, vals, live = rmi_scan_range_pallas(
            *args, page_size=page_size, max_pages=max_pages,
            interpret=interpret,
        )
    else:
        keys, vals, live = ref.rmi_scan_range_reference(
            *args, page_size=page_size, max_pages=max_pages,
        )
    return keys, vals, live.astype(bool)


def rmi_sharded_scan_page_op(
    bounds, base_keys, base_vals, live_prefix, ins_keys, ins_vals,
    ins_rank, *, page_size=256, max_pages=1, use_kernel=True,
    interpret=None, strategy=None,
):
    """Sharded fused scan: ONE device dispatch ranks ``bounds`` on
    every shard, prefix-sums the per-shard spans into stream ownership,
    gathers each shard's rows (grid kernel with the shard axis as a
    grid dimension, or the vmapped XLA fallback sharing the same
    body), and reduces the (S, G, P) owner-masked matrices into the
    global (G, P) page stream — the scan twin of the ``sharded_fused``
    lookup.  All inputs are stacked per-shard slabs in ONE shared
    normalized frame (see `index_service.scan.pack_scan_slab`); rows
    come back in that frame.  Returns ``(keys (G,P) f32, vals i32,
    live_mask bool)``; pages past the range are fully masked.
    """
    args = (
        jnp.asarray(bounds, jnp.float32),
        jnp.asarray(base_keys, jnp.float32),
        jnp.asarray(base_vals, jnp.int32),
        jnp.asarray(live_prefix, jnp.int32),
        jnp.asarray(ins_keys, jnp.float32),
        jnp.asarray(ins_vals, jnp.int32),
        jnp.asarray(ins_rank, jnp.int32),
    )

    def run_fallback():
        with dispatch_span(
            "rmi_sharded_scan_page", kernel=False, strategy=strategy,
        ):
            return _sharded_scan_jit(
                *args, page_size=page_size, max_pages=max_pages,
                use_kernel=False, interpret=interpret,
            )

    if not use_kernel:
        return run_fallback()

    def run_kernel():
        with dispatch_span(
            "rmi_sharded_scan_page", kernel=True, strategy=strategy,
        ):
            return _sharded_scan_jit(
                *args, page_size=page_size, max_pages=max_pages,
                use_kernel=True, interpret=interpret,
            )

    return run_with_failover(
        "rmi_sharded_scan_page", strategy, run_kernel, run_fallback,
    )


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "max_pages", "use_kernel", "interpret"),
)
def _sharded_scan_jit(
    bounds, base_keys, base_vals, live_prefix, ins_keys, ins_vals,
    ins_rank, *, page_size, max_pages, use_kernel, interpret,
):
    steps = _search_steps(base_keys.shape[1])
    isteps = _search_steps(ins_keys.shape[1])

    # rank pre-pass: each shard's local ranks of [lo, hi) — all keys in
    # lower shards sort below both bounds, so the per-shard spans
    # concatenate into the global stream and their prefix sums are the
    # ownership offsets (same program, no host round-trip)
    def rank_one(base, lp, ins):
        return _merged_rank_from_prefix(
            bounds, _xla(base), _xla(lp), _xla(ins), steps=steps,
            isteps=isteps,
        )

    lr = jax.vmap(rank_one)(base_keys, live_prefix, ins_keys)  # (S, 2)
    ls0 = lr[:, 0]
    ls1 = jnp.maximum(lr[:, 1], ls0)  # inverted ranges clamp empty
    span = ls1 - ls0
    pre = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(span)])
    own_lo, own_hi = pre[:-1], pre[1:]

    if use_kernel:
        keys, vals, live = rmi_sharded_scan_page_pallas(
            base_keys, base_vals, live_prefix, ins_keys, ins_vals,
            ins_rank, ls0, own_lo, own_hi,
            page_size=page_size, max_pages=max_pages, interpret=interpret,
        )
    else:
        keys, vals, live = ref.rmi_sharded_scan_page_reference(
            base_keys, base_vals, live_prefix, ins_keys, ins_vals,
            ins_rank, ls0, own_lo, own_hi,
            page_size=page_size, max_pages=max_pages,
        )
    # exactly one shard owns each stream slot: min/sum/max reassemble
    return (
        jnp.min(keys, axis=0), jnp.sum(vals, axis=0),
        jnp.max(live, axis=0).astype(bool),
    )


def rmi_sharded_routed_lookup_op(
    q_stacked, shard_of, stage0, leaf_w, leaf_b, err_lo, err_hi,
    sorted_keys, delta_keys, delta_prefix, shard_n, shard_m, shard_ratio,
    base_off, merged_off, *, hidden=(), max_window, block_q=1024,
    interpret=None, use_kernel=True, strategy=None,
):
    """Sharded merged lookup + routed reassembly in ONE device
    dispatch: the grid kernel (or vmapped fallback) and
    `sharded_reassemble` lower into a single jitted program, where the
    previous two-call path paid a second dispatch (and an HBM
    round-trip of the full (S, B) local-rank matrices) just to gather
    the routed rows.  Returns global ``(base_rank, merged_rank)``."""
    args = (
        jnp.asarray(q_stacked),
        jnp.asarray(shard_of, jnp.int32),
        tuple(jnp.asarray(p) for p in stage0),
        jnp.asarray(leaf_w), jnp.asarray(leaf_b),
        jnp.asarray(err_lo), jnp.asarray(err_hi),
        jnp.asarray(sorted_keys),
        jnp.asarray(delta_keys), jnp.asarray(delta_prefix),
        jnp.asarray(shard_n), jnp.asarray(shard_m),
        jnp.asarray(shard_ratio),
        jnp.asarray(base_off), jnp.asarray(merged_off),
    )

    def run_fallback():
        with dispatch_span(
            "rmi_sharded_routed_lookup", kernel=False,
            strategy=strategy or "sharded_fused",
        ):
            return _sharded_routed_jit(
                *args, hidden=tuple(hidden), max_window=max_window,
                block_q=block_q, interpret=interpret, use_kernel=False,
            )

    if not use_kernel:
        return run_fallback()

    def run_kernel():
        with dispatch_span(
            "rmi_sharded_routed_lookup", kernel=True,
            strategy=strategy or "sharded_fused",
        ):
            return _sharded_routed_jit(
                *args, hidden=tuple(hidden), max_window=max_window,
                block_q=block_q, interpret=interpret, use_kernel=True,
            )

    return run_with_failover(
        "rmi_sharded_routed_lookup", strategy or "sharded_fused",
        run_kernel, run_fallback,
    )


@functools.partial(
    jax.jit,
    static_argnames=("hidden", "max_window", "block_q", "interpret",
                     "use_kernel"),
)
def _sharded_routed_jit(
    q, shard_of, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys,
    delta_keys, delta_prefix, shard_n, shard_m, shard_ratio, base_off,
    merged_off, *, hidden, max_window, block_q, interpret, use_kernel,
):
    if use_kernel:
        lb, ct = rmi_sharded_merged_lookup_pallas(
            q, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys,
            delta_keys, delta_prefix, shard_n, shard_m, shard_ratio,
            hidden=hidden, max_window=max_window, block_q=block_q,
            interpret=interpret,
        )
    elif q.shape[1] == 0:
        lb = ct = jnp.zeros(q.shape, jnp.int32)
    else:
        lb, ct = ref.rmi_sharded_merged_lookup_reference(
            q, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys,
            delta_keys, delta_prefix, shard_n, shard_m, shard_ratio,
            max_window=max_window,
        )
    return sharded_reassemble(lb, ct, shard_of, base_off, merged_off)


def bloom_probe_op(bf, queries_u32, *, interpret=None):
    """Batched Bloom probe via kernel.  `bf` is a core.BloomFilter."""
    return bloom_probe_pallas(
        jnp.asarray(queries_u32),
        jnp.asarray(bf.words),
        num_bits=bf.num_bits,
        k=bf.num_hashes,
        interpret=interpret,
    )


def hash_probe_op(hm, index, keys, q_raw, *, interpret=None):
    """Batched hash-model probe.  `hm` HashMap, `index` linear-stage RMI."""
    kn = keys.normalize(q_raw)
    slot_key_norm = keys.normalize(hm.slot_key)  # NaN-safe: NaN != q
    ovf_key_norm = keys.normalize(hm.ovf_key)
    return hash_probe_pallas(
        jnp.asarray(kn),
        jnp.asarray(index.stage0_params["w0"]),
        jnp.asarray(index.stage0_params["b0"]),
        jnp.asarray(index.leaf_w),
        jnp.asarray(index.leaf_b),
        jnp.asarray(slot_key_norm),
        jnp.asarray(hm.slot_next.astype("int32")),
        jnp.asarray(ovf_key_norm),
        jnp.asarray(hm.ovf_next.astype("int32")),
        n=index.n,
        num_leaves=index.num_leaves,
        num_slots=hm.num_slots,
        trips=max(0, hm.max_chain - 1),
        interpret=interpret,
    )


def attention_op(q, k, v, *, causal=True, use_kernel=True, interpret=None,
                 blk_q=128, blk_k=128):
    """GQA attention: flash kernel when shapes tile; reference otherwise."""
    s = q.shape[2]
    if use_kernel and s % min(blk_q, s) == 0 and s >= 8:
        bq, bk = min(blk_q, s), min(blk_k, s)
        if s % bq == 0 and s % bk == 0:
            return flash_attention(
                q, k, v, causal=causal, blk_q=bq, blk_k=bk, interpret=interpret
            )
    return ref.mha_reference(q, k, v, causal=causal)
