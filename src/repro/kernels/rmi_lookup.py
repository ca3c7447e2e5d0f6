"""Fused RMI lookup Pallas kernels: stage-0 MLP + leaf FMA + bounded
search, optionally merged with the delta-buffer prefix search.

This is the paper's hot spot (§2.1's back-of-envelope: the model must
beat ~50 cycles/B-Tree-node) moved to where the paper says it belongs —
an ML accelerator.  Two kernels share one body:

``rmi_lookup_pallas`` — the read-only §3 lookup.  One invocation
performs, for a tile of queries entirely inside the core:

  1. stage-0 MLP (f32 multiply-adds, `_stage0`),
  2. leaf-model selection (reads from the SoA leaf arrays),
  3. leaf FMA -> position + error window,
  4. fixed-trip-count branchless binary search over the sorted keys.

``rmi_merged_lookup_pallas`` — the writable-index hot path (§3.3).
Steps 1-4 plus, still inside the same kernel invocation:

  5. fixed-trip branchless lower bound over the fused delta key array
     (staged inserts and tombstones, +inf-padded to a power of two),
  6. one prefix-weight read: ``merged = base_lb + prefix[delta_lb]``.

Emitting ``(base_lb, merged_rank)`` from one ``pallas_call`` removes
the second XLA dispatch and the HBM round-trip for the base lower
bound that the two-dispatch merged lookup pays — exactly the overhead
"Benchmarking Learned Indexes" shows erasing learned-index wins.

Layout on the TPU.  Mosaic has no general vector gather, so every
body here is written once against `_Flat` readers and runs two ways:

  * in XLA (the references in `ref.py` and the vmapped fallbacks) the
    reader is ``jnp.take`` and a call handles a whole batch of queries;
  * in the kernels each query (or scan row) is a scalar walked by a
    `lax.fori_loop`: queries and results sit in SMEM, stage-0 weights
    and per-shard sizes are SMEM scalars, and every array the searches
    probe is a lane-major ``(R, 128)`` VMEM slab (`_rows`) read one
    element at a time through a dynamic row slice and a masked lane
    reduction (`_slab`).

Both run the same arithmetic in the same order, so kernel and XLA
results are bit-identical for every input.  Slabs are whole-array VMEM
operands (single-buffered); the stacked per-shard slabs are blocked by
shard row.  The largest index a kernel takes is therefore bounded by
the core's scoped VMEM: the compile tests and ``chip_smoke.py`` find
it with the TPU compiler.  ``interpret=None`` compiles through Mosaic
on a TPU and runs Pallas interpret mode everywhere else.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128
SUBLANES = 8
SMEM_BLOCK = 1024  # partial 1-D SMEM blocks: multiples of this


def _search_steps(max_window: int) -> int:
    return max(1, int(math.ceil(math.log2(max(2, max_window + 1)))) + 1)


def default_interpret() -> bool:
    """True off-TPU, where Pallas kernels run in interpret mode."""
    return resolve_interpret(None)


# ---------------------------------------------------------------------------
# readers: one body, XLA arrays or kernel slabs
# ---------------------------------------------------------------------------

class _Flat(NamedTuple):
    """A flat array seen through ``read(i)`` for int32 indices in
    ``[0, size)`` — elementwise over any index shape in XLA, one scalar
    at a time inside a kernel."""

    read: Callable
    size: int


def _xla(a: jnp.ndarray) -> _Flat:
    return _Flat(lambda i: jnp.take(a, i), a.shape[-1])


def _rows(a: jnp.ndarray, pad=0) -> jnp.ndarray:
    """``(..., N) -> (..., R, 128)`` lane-major rows, R a multiple of 8:
    the VMEM slab layout every kernel reads with `_slab`."""
    n = a.shape[-1]
    tile = SUBLANES * LANES
    r = max(1, -(-n // tile)) * SUBLANES
    widths = [(0, 0)] * (a.ndim - 1) + [(0, r * LANES - n)]
    a = jnp.pad(a, widths, constant_values=pad)
    return a.reshape(a.shape[:-1] + (r, LANES))


def _slab(ref, size: int, lead: Tuple[int, ...] = ()) -> _Flat:
    """Reader over a `_rows` slab in VMEM: element ``i`` is lane
    ``i % 128`` of row ``i // 128``, taken by a dynamic one-row slice
    and a masked lane reduction (max for floats keeps -0.0 and NaN)."""
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    floating = jnp.issubdtype(ref.dtype, jnp.floating)

    def read(i):
        row = ref[lead + (pl.ds(lax.shift_right_logical(i, 7), 1),
                          slice(None))]
        hit = lane == (i & (LANES - 1))
        if floating:
            return jnp.max(jnp.where(hit, row, -jnp.inf))
        return jnp.sum(jnp.where(hit, row, 0))

    return _Flat(read, size)


def _flat_params(stage0) -> jnp.ndarray:
    """(w0, b0, w1, b1, ...) -> one flat f32 vector (row-major weights),
    the SMEM layout `_stage0` reads by static offset; a stacked
    ``(S, ...)`` tuple flattens per shard to ``(S, P)``."""
    lead = stage0[0].shape[:-2]
    return jnp.concatenate(
        [p.reshape(lead + (-1,)).astype(jnp.float32) for p in stage0],
        axis=-1,
    )


def _stage0(q, param: Callable, hidden: Tuple[int, ...]):
    """Stage-0 MLP as explicit f32 multiply-adds, one hidden unit at a
    time: ``out_j = (sum_k h_k * w[k, j]) + b_j`` accumulated in k
    order, ReLU between layers.  ``param(off)`` reads the flat
    `_flat_params` vector.  The same order as
    `core.models.dense_f32`, so the build's leaf assignment, the XLA
    lookups, and the kernels agree bit for bit on every backend."""
    dims = (1, *hidden, 1)
    h = [q]
    off = 0
    for layer, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        bias = off + a * b
        out = []
        for j in range(b):
            acc = h[0] * param(off + j)
            for k in range(1, a):
                acc = acc + h[k] * param(off + k * b + j)
            acc = acc + param(bias + j)
            out.append(jnp.maximum(acc, 0.0) if layer < len(dims) - 2
                       else acc)
        h = out
        off = bias + b
    return h[0]


# ---------------------------------------------------------------------------
# shared bodies
# ---------------------------------------------------------------------------

def _rmi_lower_bound(
    q,
    param: Callable,             # flat stage-0 reader (see `_stage0`)
    hidden: Tuple[int, ...],
    leaf_w: _Flat,
    leaf_b: _Flat,
    err_lo: _Flat,
    err_hi: _Flat,
    keys: _Flat,                 # sorted; reads clipped to [0, n)
    *,
    n,                           # true base size (static int or traced)
    m,                           # true leaf count
    ratio,                       # m / n: python float or host f32(m / n)
    top,                         # float(n - 1): the position clip
    steps: int,
    clamp: bool,
):
    """Stage-0 MLP -> leaf FMA -> first probe at the prediction (model
    binary search, §3.4) -> fixed-trip bounded search.  ``clamp``
    applies the sharded path's final ``minimum(lo, n)``: there ``steps``
    is the max over shards, and extra trips past a converged ``lo == n``
    overshoot by one."""
    p0 = _stage0(q, param, hidden)
    leaf = jnp.clip(jnp.floor(p0 * ratio).astype(jnp.int32), 0, m - 1)
    pos = jnp.clip(leaf_w.read(leaf) * q + leaf_b.read(leaf), 0.0, top)
    lo = jnp.clip((pos + err_lo.read(leaf)).astype(jnp.int32), 0, n)
    hi = jnp.clip((pos + err_hi.read(leaf)).astype(jnp.int32) + 1, 0, n)

    p0i = jnp.clip(pos.astype(jnp.int32), 0, n - 1)
    right = keys.read(p0i) < q
    lo = jnp.where(right, jnp.maximum(lo, p0i + 1), lo)
    hi = jnp.where(right, hi, jnp.minimum(hi, p0i))

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        r = keys.read(jnp.clip(mid, 0, n - 1)) < q
        return jnp.where(r, mid + 1, lo), jnp.where(r, hi, mid)

    lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
    return jnp.minimum(lo, n) if clamp else lo


def _delta_lower_bound(q, dkeys: _Flat, *, dsteps: int):
    """Full-range branchless lower bound over the padded delta keys
    (+inf pads sort after every finite query)."""
    d = dkeys.size
    lo = jnp.zeros(jnp.shape(q), jnp.int32)
    hi = jnp.full(jnp.shape(q), d, jnp.int32)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        r = dkeys.read(jnp.clip(mid, 0, d - 1)) < q
        return jnp.where(r, mid + 1, lo), jnp.where(r, hi, mid)

    lo, hi = lax.fori_loop(0, dsteps, body, (lo, hi))
    return lo


def _array_lower_bound(arr: _Flat, q, size, steps: int):
    """Branchless lower bound of each q in arr[0:size] (float or int
    arrays; fixed trip count).  Unlike the key-search loops, scan
    queries may equal or exceed every stored element (q = +inf
    sentinels, position queries past the pad), so the converged state
    is pinned with ``lo < hi`` — extra trips past convergence must not
    walk ``lo`` off the end."""
    lo = jnp.zeros(jnp.shape(q), jnp.int32)
    hi = jnp.full(jnp.shape(q), size, jnp.int32)

    def body(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        v = arr.read(jnp.clip(mid, 0, size - 1))
        r = (v < q) & (lo < hi)
        return jnp.where(r, mid + 1, lo), jnp.where(r, hi, mid)

    lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def _scan_page_body(
    t,                           # int32 target merged ranks (any shape)
    base_keys: _Flat,            # (N,) sorted normalized f32 base keys
    base_vals: _Flat,            # (N,) int32 payload aligned with base
    ins_keys: _Flat,             # (Di,) sorted eff. insert keys, +inf pad
    ins_vals: _Flat,             # (Di,) int32 staged values (0 on pads)
    del_pos: _Flat,              # (Dd,) sorted dead base positions, n pad
    end_rank,                    # () int32 — one past the last live rank
    *,
    steps: int,
    isteps: int,
    dsteps: int,
):
    """One merged row per target rank, without materializing the merge.

    The live merged array is A ∪ C with A = base minus the dead
    positions (``del_pos``) and C = the effective staged inserts —
    disjoint by construction (`delta.collapse_levels`), so every rank
    decomposes uniquely.  Per slot t:

      1. partition:  j = |{c ∈ C : merged_rank(c) < t}| by binary
         search on j over  merged_rank(C[j]) = j + a_before(C[j]),
         where a_before(x) = lower_bound(base, x) - dead_before;
      2. select:     the (t-j)-th live base row by binary search over
         base positions with live_before(p) = p - lower_bound(del_pos, p);
      3. emit        C[j] where C[j] <= A[t-j], else A[t-j], with its
         source's value; slots at or past ``end_rank`` are masked dead
         (+inf key, 0 value).

    The tie rule: ``a_before`` is a float32 lower bound, so the
    partition ranks a staged insert before every base key whose image
    it ties, and the select must then prefer the insert on a tie, or
    it emits the tied base row at the insert's rank and again at the
    next one.

    Fixed trip counts everywhere, so the same body runs inside the
    Pallas kernel and the XLA fallback with bit-identical results.
    """
    inf = jnp.float32(jnp.inf)
    n, ni, nd = base_keys.size, ins_keys.size, del_pos.size

    # ---- partition: inserts among the first t merged rows -------------
    lo = jnp.zeros(jnp.shape(t), jnp.int32)
    hi = jnp.full(jnp.shape(t), ni, jnp.int32)

    def jbody(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        ck = ins_keys.read(jnp.clip(mid, 0, ni - 1))
        ck = jnp.where(mid >= ni, inf, ck)
        bl = _array_lower_bound(base_keys, ck, n, steps)
        dl = _array_lower_bound(del_pos, bl, nd, dsteps)
        pred = mid + (bl - dl) >= t
        adv = ~pred & (lo < hi)  # converged lanes stay pinned
        return jnp.where(adv, mid + 1, lo), jnp.where(pred, mid, hi)

    j, _ = lax.fori_loop(0, isteps, jbody, (lo, hi))
    i = t - j

    # ---- select: the i-th live base position --------------------------
    lo = jnp.zeros(jnp.shape(t), jnp.int32)
    hi = jnp.full(jnp.shape(t), n, jnp.int32)

    def pbody(_, state):
        lo, hi = state
        mid = (lo + hi) // 2
        dl = _array_lower_bound(del_pos, mid + 1, nd, dsteps)
        pred = (mid + 1 - dl) >= (i + 1)
        adv = ~pred & (lo < hi)
        return jnp.where(adv, mid + 1, lo), jnp.where(pred, mid, hi)

    p, _ = lax.fori_loop(0, steps, pbody, (lo, hi))

    a_key = jnp.where(p >= n, inf, base_keys.read(jnp.clip(p, 0, n - 1)))
    a_val = base_vals.read(jnp.clip(p, 0, n - 1))
    c_key = jnp.where(j >= ni, inf, ins_keys.read(jnp.clip(j, 0, ni - 1)))
    c_val = ins_vals.read(jnp.clip(j, 0, ni - 1))

    from_ins = c_key <= a_key   # the tie rule (docstring)
    live = ((t >= 0) & (t < end_rank)).astype(jnp.int32)
    key = jnp.where(from_ins, c_key, a_key)
    val = jnp.where(from_ins, c_val, a_val)
    key = jnp.where(live == 1, key, inf)
    val = jnp.where(live == 1, val, 0)
    return key, val, live


def _merged_rank(
    q,                           # f32 queries (any shape), normalized frame
    base_keys: _Flat,            # (N,) sorted f32, +inf past the true size
    dead_pos: _Flat,             # (T,) sorted dead base positions, big pad
    ins_keys: _Flat,             # (D,) sorted eff. insert keys, +inf pad
    *,
    steps: int,
    tsteps: int,
    isteps: int,
):
    """Merged lower-bound rank of each query:

        bl = lower_bound(base, q)
        rank(q) = bl - lower_bound(dead_pos, bl) + lower_bound(ins, q)

    — the device-side twin of `PinnedView.rank`, so scan endpoints never
    round-trip through host NumPy.  The tombstone search is the size of
    the delta, not of the base; every search is fixed-trip and pad-safe
    (+inf key pads and the big position pad sort past every query)."""
    bl = _array_lower_bound(base_keys, q, base_keys.size, steps)
    dead = _array_lower_bound(dead_pos, bl, dead_pos.size, tsteps)
    ins = _array_lower_bound(ins_keys, q, ins_keys.size, isteps)
    return bl - dead + ins


def _scan_rows(
    t,                           # int32 target merged ranks (any shape)
    valid,                       # bool: lanes that hold a live row
    base_keys: _Flat,            # (N,) sorted f32, +inf past the true size
    base_vals: _Flat,            # (N,) int32 payload aligned with base
    ins_keys: _Flat,             # (D,) sorted eff. insert keys, +inf pad
    ins_vals: _Flat,             # (D,) int32 staged values (0 on pads)
    ins_rank: _Flat,             # (D,) i32 merged rank of insert j, big pad
    dead_rank: _Flat,            # (T,) i32 live base rows below dead j, big pad
    *,
    msteps: int,
    tsteps: int,
):
    """One merged row per target rank, resolved through two searches
    over delta-sized arrays (host-precomputed per delta version):

      1. partition:  j = lower_bound(ins_rank, t) — ``ins_rank[j] =
         j + live_base_before(ins[j])`` is the merged rank of staged
         insert j, strictly increasing;
      2. select:     the a-th live base row, a = t - j, is at position
         ``p = a + upper_bound(dead_rank, a)``: with ``dead_rank[k] =
         dead_pos[k] - k`` the live rows below dead position k
         (nondecreasing), exactly the dead positions whose
         ``dead_rank`` is at most a lie below that row;
      3. emit        the insert row where its key is at most the base
         row's, else the base row, with its source's value; rows with
         ``valid`` False are masked dead (+inf key, 0 val).

    Decomposition identical to `_scan_page_body` (same j, same base
    position, same tie rule: ``ins_rank`` counts base rows below an
    insert by a float32 lower bound, so an insert that ties a base
    key's image ranks before it, and the select prefers the insert on
    a tie), so rows match the NumPy merge oracle.
    """
    inf = jnp.float32(jnp.inf)
    n, ni = base_keys.size, ins_keys.size

    with jax.named_scope("delta_search"):
        j = _array_lower_bound(ins_rank, t, ni, msteps)
        a_i = t - j
        # dead_rank holds integers: upper_bound(a) = lower_bound(a + 1)
        p = a_i + _array_lower_bound(dead_rank, a_i + 1, dead_rank.size,
                                     tsteps)

    with jax.named_scope("row_gather"):
        a_key = jnp.where(
            (p < 0) | (p >= n), inf, base_keys.read(jnp.clip(p, 0, n - 1))
        )
        a_val = base_vals.read(jnp.clip(p, 0, n - 1))
        c_key = jnp.where(j >= ni, inf,
                          ins_keys.read(jnp.clip(j, 0, ni - 1)))
        c_val = ins_vals.read(jnp.clip(j, 0, ni - 1))

        from_ins = c_key <= a_key   # the tie rule (docstring)
        live = jnp.asarray(valid).astype(jnp.int32)
        key = jnp.where(from_ins, c_key, a_key)
        val = jnp.where(from_ins, c_val, a_val)
        key = jnp.where(live == 1, key, inf)
        val = jnp.where(live == 1, val, 0)
    return key, val, live


def _shard_lookup(q, param, hidden, leaf_w, leaf_b, err_lo, err_hi, keys,
                  dkeys, dprefix, n, m, ratio, *, steps, dsteps):
    """One shard of the sharded merged lookup: `_rmi_lower_bound` with
    the static (n, num_leaves) promoted to traced per-shard scalars, so
    heterogeneous shards stack on one axis (the kernel's grid, or the
    vmap of `ref.rmi_sharded_merged_lookup_reference`).

    ``ratio`` must be ``np.float32(m / n)`` computed on the host — the
    same f64-divide-then-round the static kernel's weak-typed
    ``num_leaves / n`` python float performs — so leaf selection stays
    bit-identical to build-time leaf assignment (the window contract).
    Returns ``(base_lb, delta_prefix_contribution)``; callers add the
    global shard offsets (see `ops.sharded_reassemble`)."""
    lb = _rmi_lower_bound(
        q, param, hidden, leaf_w, leaf_b, err_lo, err_hi, keys,
        n=n, m=m, ratio=ratio, top=n.astype(jnp.float32) - 1.0,
        steps=steps, clamp=True,
    )
    dlb = _delta_lower_bound(q, dkeys, dsteps=dsteps)
    return lb, dprefix.read(dlb)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _smem(block=None, index_map=None) -> pl.BlockSpec:
    if block is None:
        return pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.BlockSpec(block, index_map, memory_space=pltpu.SMEM)


def _vmem() -> pl.BlockSpec:
    """Whole-array VMEM operand, copied in once (no double buffer)."""
    return pl.BlockSpec(memory_space=pltpu.VMEM)


def _shard_rows(a: jnp.ndarray) -> pl.BlockSpec:
    """One shard's ``(1, R, 128)`` block of a stacked slab, following
    grid axis 0 (legal under the tiling rule: the last two block dims
    are the full dims)."""
    return pl.BlockSpec(
        (1,) + a.shape[1:],
        lambda si, *_: (si, 0, 0),
    )


def _for_each(count: int, fn) -> None:
    """Run ``fn(i)`` for i in [0, count) — one query or scan row each."""
    def body(i, carry):
        fn(i)
        return carry

    lax.fori_loop(0, count, body, 0)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile(b: int, block_q: int, whole: bool = True) -> Tuple[int, int]:
    """Query tile and the padded batch it divides.  A batch that fits
    one tile is one whole-array block (padded to 128 lanes) where
    ``whole`` says the tile is the whole operand; otherwise the tile is
    a multiple of 1024, the tiling XLA gives a 1-D operand and so the
    only partial SMEM block size Mosaic accepts for it."""
    if whole and b <= block_q:
        bq = _round_up(b, LANES)
        return bq, bq
    bq = _round_up(min(b, block_q), SMEM_BLOCK)
    return bq, _round_up(b, bq)


def _lookup_kernel(*refs, hidden, n, num_leaves, steps, dsteps, dsize,
                   nleaf, bq):
    merged = dsteps is not None
    q_ref, p_ref, lw, lb, elo, ehi, keys = refs[:7]
    leaves = [_slab(r, nleaf) for r in (lw, lb, elo, ehi)]
    keys = _slab(keys, n)
    if merged:
        dkeys, dprefix = _slab(refs[7], dsize), _slab(refs[8], dsize + 1)
        base_out, merged_out = refs[9], refs[10]
    else:
        base_out = refs[7]

    def one(i):
        q = q_ref[i]
        lb_ = _rmi_lower_bound(
            q, lambda off: p_ref[off], hidden, *leaves, keys,
            n=n, m=num_leaves, ratio=num_leaves / n, top=float(n - 1),
            steps=steps, clamp=False,
        )
        base_out[i] = lb_
        if merged:
            dlb = _delta_lower_bound(q, dkeys, dsteps=dsteps)
            merged_out[i] = lb_ + dprefix.read(dlb)

    _for_each(bq, one)


def _lookup_call(q, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys,
                 delta, *, hidden, n, num_leaves, max_window, block_q,
                 interpret):
    b = q.shape[0]
    bq, padded = _tile(b, block_q)
    q = jnp.pad(q, (0, padded - b))
    dsteps = dsize = None
    operands = [q, _flat_params(stage0)]
    operands += [_rows(a) for a in (leaf_w, leaf_b, err_lo, err_hi)]
    operands += [_rows(sorted_keys, jnp.inf)]
    if delta is not None:
        dkeys, dprefix = delta
        dsize = dkeys.shape[0]
        dsteps = _search_steps(dsize)
        operands += [_rows(dkeys, jnp.inf), _rows(dprefix)]
    tile = _smem((bq,), lambda i: (i,))
    outs = 1 if delta is None else 2
    res = pl.pallas_call(
        functools.partial(
            _lookup_kernel, hidden=hidden, n=n, num_leaves=num_leaves,
            steps=_search_steps(max_window), dsteps=dsteps, dsize=dsize,
            nleaf=leaf_w.shape[0], bq=bq,
        ),
        grid=(padded // bq,),
        in_specs=[tile, _smem()] + [_vmem()] * (len(operands) - 2),
        out_specs=[tile] * outs,
        out_shape=[jax.ShapeDtypeStruct((padded,), jnp.int32)] * outs,
        interpret=resolve_interpret(interpret),
    )(*operands)
    return tuple(r[:b] for r in res)


@functools.partial(
    jax.jit,
    static_argnames=("hidden", "n", "num_leaves", "max_window", "block_q", "interpret"),
)
def rmi_lookup_pallas(
    q: jax.Array,                      # (B,) normalized queries
    stage0: Tuple[jax.Array, ...],     # (w0, b0, w1, b1, ...) flattened
    leaf_w: jax.Array,                 # (M,)
    leaf_b: jax.Array,                 # (M,)
    err_lo: jax.Array,                 # (M,)
    err_hi: jax.Array,                 # (M,)
    sorted_keys: jax.Array,            # (N,)
    *,
    hidden: Tuple[int, ...],
    n: int,
    num_leaves: int,
    max_window: int,
    block_q: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if q.shape[0] == 0:  # degenerate batch: nothing to tile
        return jnp.zeros((0,), jnp.int32)
    (base,) = _lookup_call(
        q, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys, None,
        hidden=hidden, n=n, num_leaves=num_leaves, max_window=max_window,
        block_q=block_q, interpret=interpret,
    )
    return base


@functools.partial(
    jax.jit,
    static_argnames=("hidden", "n", "num_leaves", "max_window", "block_q", "interpret"),
)
def rmi_merged_lookup_pallas(
    q: jax.Array,                      # (B,) normalized queries
    stage0: Tuple[jax.Array, ...],     # (w0, b0, w1, b1, ...) flattened
    leaf_w: jax.Array,                 # (M,)
    leaf_b: jax.Array,                 # (M,)
    err_lo: jax.Array,                 # (M,)
    err_hi: jax.Array,                 # (M,)
    sorted_keys: jax.Array,            # (N,)
    delta_keys: jax.Array,             # (D,) +inf-padded pow2 (combine_for_device)
    delta_prefix: jax.Array,           # (D+1,) int32 net +1/-1 prefix
    *,
    hidden: Tuple[int, ...],
    n: int,
    num_leaves: int,
    max_window: int,
    block_q: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused base+delta merged lookup: one kernel, two outputs.

    Returns ``(base_lb, merged_rank)`` — the RMI lower bound in the
    base array plus the merged rank after the staged delta's +1/-1
    prefix contribution.  Retraces per (index, delta capacity bucket):
    ``delta_keys`` comes +inf-padded to a power of two, so the jit
    cache is keyed by bucket, never by individual writes.
    """
    if q.shape[0] == 0:  # degenerate batch: nothing to tile
        empty = jnp.zeros((0,), jnp.int32)
        return empty, empty
    return _lookup_call(
        q, stage0, leaf_w, leaf_b, err_lo, err_hi, sorted_keys,
        (delta_keys, delta_prefix),
        hidden=hidden, n=n, num_leaves=num_leaves, max_window=max_window,
        block_q=block_q, interpret=interpret,
    )


def _sharded_kernel(q_ref, p_ref, lw, lb, elo, ehi, keys, dkeys, dprefix,
                    n_ref, m_ref, ratio_ref, base_out, contrib_out, *,
                    hidden, nparams, sizes, steps, dsteps, rows):
    si = pl.program_id(0)
    nleaf, nkeys, dsize = sizes
    readers = [_slab(r, nleaf, (0,)) for r in (lw, lb, elo, ehi)]
    readers += [_slab(keys, nkeys, (0,)), _slab(dkeys, dsize, (0,)),
                _slab(dprefix, dsize + 1, (0,))]
    n, m, ratio = n_ref[si], m_ref[si], ratio_ref[si]
    p0 = si * nparams

    def one(i):
        lb_, ct = _shard_lookup(
            q_ref[i], lambda off: p_ref[p0 + off], hidden, *readers,
            n, m, ratio, steps=steps, dsteps=dsteps,
        )
        base_out[i] = lb_
        contrib_out[i] = ct

    _for_each(rows, one)


@functools.partial(
    jax.jit,
    static_argnames=("hidden", "max_window", "block_q", "interpret"),
)
def rmi_sharded_merged_lookup_pallas(
    q: jax.Array,                      # (S, B) per-shard normalized queries
    stage0: Tuple[jax.Array, ...],     # (w0, b0, ...) each stacked (S, ...)
    leaf_w: jax.Array,                 # (S, M) zero-padded past each shard's m
    leaf_b: jax.Array,                 # (S, M)
    err_lo: jax.Array,                 # (S, M)
    err_hi: jax.Array,                 # (S, M)
    sorted_keys: jax.Array,            # (S, N) padded; pads unread (clip by n)
    delta_keys: jax.Array,             # (S, D) +inf-padded per-shard deltas
    delta_prefix: jax.Array,           # (S, D+1) prefix, constant on pad tail
    shard_n: jax.Array,                # (S,) int32 true base sizes
    shard_m: jax.Array,                # (S,) int32 true leaf counts
    shard_ratio: jax.Array,            # (S,) float32 — f32(m/n) per shard
    *,
    hidden: Tuple[int, ...],
    max_window: int,                   # max over shards (extra trips clamped)
    block_q: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Sharded merged lookup: grid = (shard, query tile), ONE pallas_call.

    Every query tile is evaluated on every shard row (the shard axis is
    a grid dimension; there is no data-dependent per-shard gather
    inside the kernel).  Returns the per-shard local ``(base_lb,
    delta_prefix_contribution)`` matrices, both (S, B);
    `ops.sharded_reassemble` selects each query's routed row and adds
    the global prefix-sum offsets.  Static shapes are the padded maxima
    — per-shard true sizes travel as SMEM scalars, so one jit cache
    entry serves heterogeneous shards.
    """
    s, b = q.shape
    if b == 0:
        empty = jnp.zeros((s, 0), jnp.int32)
        return empty, empty
    # every shard row is its own block of the flat query operand
    bq, padded = _tile(b, block_q, whole=False)
    t = padded // bq
    q = jnp.pad(q, ((0, 0), (0, padded - b))).reshape(-1)
    params = _flat_params(stage0)
    slabs = [_rows(a) for a in (leaf_w, leaf_b, err_lo, err_hi)]
    slabs += [_rows(sorted_keys, jnp.inf), _rows(delta_keys, jnp.inf),
              _rows(delta_prefix)]
    tile = _smem((bq,), lambda si, ti: (si * t + ti,))
    base, contrib = pl.pallas_call(
        functools.partial(
            _sharded_kernel, hidden=hidden, nparams=params.shape[1],
            sizes=(leaf_w.shape[1], sorted_keys.shape[1],
                   delta_keys.shape[1]),
            steps=_search_steps(max_window),
            dsteps=_search_steps(delta_keys.shape[1]),
            rows=min(b, bq),  # a lone tile's pad lanes are sliced off
        ),
        grid=(s, t),
        in_specs=[tile, _smem()] + [_shard_rows(a) for a in slabs]
        + [_smem()] * 3,
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((s * padded,), jnp.int32)] * 2,
        interpret=resolve_interpret(interpret),
    )(q, params.reshape(-1), *slabs, shard_n.astype(jnp.int32),
      shard_m.astype(jnp.int32), shard_ratio.astype(jnp.float32))
    return (base.reshape(s, padded)[:, :b],
            contrib.reshape(s, padded)[:, :b])


def _page_outputs(pages: int, page_size: int, index_map):
    """Flat SMEM page outputs: each grid step owns one ``row``-long
    block (page size rounded up to `SMEM_BLOCK`); the wrapper slices
    the pad back off."""
    row = _round_up(page_size, SMEM_BLOCK)
    spec = _smem((row,), index_map)
    shapes = [jax.ShapeDtypeStruct((pages * row,), dt)
              for dt in (jnp.float32, jnp.int32, jnp.int32)]
    return row, [spec] * 3, shapes


def _emit_rows(page_size, outs, row_fn) -> None:
    keys_out, vals_out, live_out = outs

    def one(l):
        key, val, live = row_fn(l)
        keys_out[l] = key
        vals_out[l] = val
        live_out[l] = live

    _for_each(page_size, one)


def _unpage(outs, lead, row, page_size):
    return tuple(o.reshape(lead + (row,))[..., :page_size] for o in outs)


def _scan_page_kernel(starts_ref, bk, bv, ik, iv, dp, end_ref, *outs,
                      sizes, page_size, steps, isteps, dsteps):
    n, ni, nd = sizes
    flats = (_slab(bk, n), _slab(bv, n), _slab(ik, ni), _slab(iv, ni),
             _slab(dp, nd))
    start = starts_ref[pl.program_id(0)]
    end = end_ref[0]
    _emit_rows(page_size, outs, lambda l: _scan_page_body(
        start + l, *flats, end, steps=steps, isteps=isteps, dsteps=dsteps,
    ))


@functools.partial(
    jax.jit, static_argnames=("page_size", "interpret")
)
def rmi_scan_page_pallas(
    starts: jax.Array,             # (G,) int32 page start ranks
    base_keys: jax.Array,          # (N,) sorted normalized f32
    base_vals: jax.Array,          # (N,) int32
    ins_keys: jax.Array,           # (Di,) +inf-padded eff. insert keys
    ins_vals: jax.Array,           # (Di,) int32
    del_pos: jax.Array,            # (Dd,) n-padded dead base positions
    end_rank: jax.Array,           # (1,) int32
    *,
    page_size: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rank-addressed merged scan gather: grid = pages, ONE pallas_call.

    Page g emits rows at merged ranks ``starts[g] + [0, page_size)`` as
    ``(keys f32, vals i32, live i32)`` — the streaming read path that
    follows a merged-rank lookup, with the same VMEM-residency argument
    as the lookup kernels (base + delta slabs).  No RMI here: ranks
    address the merge directly, so each row is three nested fixed-trip
    binary searches plus reads.
    """
    g = starts.shape[0]
    if g == 0:
        empty = jnp.zeros((0, page_size), jnp.int32)
        return empty.astype(jnp.float32), empty, empty
    sizes = (base_keys.shape[0], ins_keys.shape[0], del_pos.shape[0])
    row, out_specs, out_shape = _page_outputs(g, page_size, lambda i: (i,))
    outs = pl.pallas_call(
        functools.partial(
            _scan_page_kernel, sizes=sizes, page_size=page_size,
            steps=_search_steps(sizes[0]), isteps=_search_steps(sizes[1]),
            dsteps=_search_steps(sizes[2]),
        ),
        grid=(g,),
        in_specs=[_smem()] + [_vmem()] * 5 + [_smem()],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(starts, _rows(base_keys, jnp.inf), _rows(base_vals),
      _rows(ins_keys, jnp.inf), _rows(ins_vals), _rows(del_pos), end_rank)
    return _unpage(outs, (g,), row, page_size)


def _scan_range_kernel(bounds_ref, bk, bv, ik, iv, ir, dp, dr, *outs,
                       sizes, page_size, steps, isteps, msteps, tsteps):
    n, d, nt = sizes
    base_keys, base_vals = _slab(bk, n), _slab(bv, n)
    ins_keys, ins_vals, ins_rank = _slab(ik, d), _slab(iv, d), _slab(ir, d)
    dead_pos, dead_rank = _slab(dp, nt), _slab(dr, nt)

    def rank(x):
        return _merged_rank(
            x, base_keys, dead_pos, ins_keys, steps=steps, tsteps=tsteps,
            isteps=isteps,
        )

    r0 = rank(bounds_ref[0])
    r1 = jnp.maximum(rank(bounds_ref[1]), r0)  # inverted ranges clamp empty
    first = r0 + pl.program_id(0) * page_size

    def row(l):
        t = first + l
        return _scan_rows(
            t, t < r1, base_keys, base_vals, ins_keys, ins_vals, ins_rank,
            dead_rank, msteps=msteps, tsteps=tsteps,
        )

    _emit_rows(page_size, outs, row)


@functools.partial(
    jax.jit, static_argnames=("page_size", "max_pages", "interpret")
)
def rmi_scan_range_pallas(
    bounds: jax.Array,             # (2,) f32 normalized [lo, hi)
    base_keys: jax.Array,          # (N,) sorted normalized f32
    base_vals: jax.Array,          # (N,) int32
    ins_keys: jax.Array,           # (D,) +inf-padded eff. insert keys
    ins_vals: jax.Array,           # (D,) int32
    ins_rank: jax.Array,           # (D,) i32 merged rank of each insert
    dead_pos: jax.Array,           # (T,) i32 sorted dead positions, big pad
    dead_rank: jax.Array,          # (T,) i32 live rows below each, big pad
    *,
    page_size: int,
    max_pages: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused scan endpoints + page gather: ONE pallas_call computes the
    merged ranks ``(r0, r1)`` of [lo, hi) *and* streams every page of
    merged rows at ranks ``r0 + [0, r1 - r0)`` — no host rank
    round-trip between ranking and gathering.  Grid = pages
    (``max_pages`` is the caller's conservative static bound; pages
    past ``r1`` come back fully masked).  Rank-to-row resolution runs
    through the delta-sized arrays the host precomputes per delta
    version (``ins_rank``, ``dead_pos``, ``dead_rank``), so each row
    costs two single-read fixed-trip searches over the delta — the
    nested tombstone searches of `rmi_scan_page_pallas` are hoisted to
    that precompute."""
    g = max_pages
    n, d, nt = base_keys.shape[0], ins_keys.shape[0], dead_pos.shape[0]
    row, out_specs, out_shape = _page_outputs(g, page_size, lambda i: (i,))
    outs = pl.pallas_call(
        functools.partial(
            _scan_range_kernel, sizes=(n, d, nt), page_size=page_size,
            steps=_search_steps(n), isteps=_search_steps(d),
            msteps=_search_steps(ins_rank.shape[0]),
            tsteps=_search_steps(nt),
        ),
        grid=(g,),
        in_specs=[_smem()] + [_vmem()] * 7,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(bounds, _rows(base_keys, jnp.inf), _rows(base_vals),
      _rows(ins_keys, jnp.inf), _rows(ins_vals), _rows(ins_rank),
      _rows(dead_pos), _rows(dead_rank))
    return _unpage(outs, (g,), row, page_size)


def _sharded_scan_kernel(bk, bv, ik, iv, ir, dr, ls0_ref, own_lo_ref,
                         own_hi_ref, *outs, sizes, page_size, msteps,
                         tsteps):
    si, gi = pl.program_id(0), pl.program_id(1)
    n, d, nt = sizes
    flats = (_slab(bk, n, (0,)), _slab(bv, n, (0,)),
             _slab(ik, d, (0,)), _slab(iv, d, (0,)),
             _slab(ir, d, (0,)), _slab(dr, nt, (0,)))
    own_lo, own_hi, ls0 = own_lo_ref[si], own_hi_ref[si], ls0_ref[si]
    first = gi * page_size

    def row(l):
        t_rel = first + l
        owner = (t_rel >= own_lo) & (t_rel < own_hi)
        return _scan_rows(
            ls0 + t_rel - own_lo, owner, *flats, msteps=msteps,
            tsteps=tsteps,
        )

    _emit_rows(page_size, outs, row)


@functools.partial(
    jax.jit, static_argnames=("page_size", "max_pages", "interpret")
)
def rmi_sharded_scan_page_pallas(
    base_keys: jax.Array,          # (S, N) sorted f32, +inf padded
    base_vals: jax.Array,          # (S, N) int32, 0 padded
    ins_keys: jax.Array,           # (S, D) +inf-padded eff. inserts
    ins_vals: jax.Array,           # (S, D) int32
    ins_rank: jax.Array,           # (S, D) i32, big pad
    dead_rank: jax.Array,          # (S, T) i32, big pad
    ls0: jax.Array,                # (S,) i32 local rank of lo per shard
    own_lo: jax.Array,             # (S,) i32 shard's first output rank
    own_hi: jax.Array,             # (S,) i32 one past its last
    *,
    page_size: int,
    max_pages: int,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sharded stacked scan gather: grid = (shard, page), ONE
    pallas_call — the scan twin of `rmi_sharded_merged_lookup_pallas`.

    Shard ranges tile the key space, so the global page stream of
    [lo, hi) is the concatenation of per-shard sub-streams; ``own_lo``
    / ``own_hi`` (prefix sums of per-shard in-range spans, computed in
    the same jitted program by `ops.rmi_sharded_scan_page_op`'s rank
    pre-pass) say which slice of the output stream each shard owns.
    Every (shard, page) grid step resolves the page's target ranks
    against its own slab through the per-shard staged-insert and
    tombstone ranks; non-owned rows emit (+inf, 0, dead), so reducing
    min/sum/max over the shard axis reassembles the global pages.
    Returns the raw (S, G, P) per-shard matrices; the op does the
    reduction."""
    s, n = base_keys.shape
    g = max_pages
    d, nt = ins_keys.shape[1], dead_rank.shape[1]
    row, out_specs, out_shape = _page_outputs(
        s * g, page_size, lambda si, gi: (si * g + gi,)
    )
    slabs = [_rows(base_keys, jnp.inf), _rows(base_vals),
             _rows(ins_keys, jnp.inf), _rows(ins_vals), _rows(ins_rank),
             _rows(dead_rank)]
    outs = pl.pallas_call(
        functools.partial(
            _sharded_scan_kernel, sizes=(n, d, nt), page_size=page_size,
            msteps=_search_steps(d), tsteps=_search_steps(nt),
        ),
        grid=(s, g),
        in_specs=[_shard_rows(a) for a in slabs] + [_smem()] * 3,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(*slabs, ls0, own_lo, own_hi)
    return _unpage(outs, (s, g), row, page_size)


def stage0_flat(params: Dict[str, np.ndarray]) -> Tuple[jax.Array, ...]:
    """RMIndex.stage0_params dict -> ordered (w0, b0, w1, b1, ...) tuple."""
    nl = len(params) // 2
    out = []
    for i in range(nl):
        out.append(jnp.asarray(params[f"w{i}"]))
        out.append(jnp.asarray(params[f"b{i}"]))
    return tuple(out)
