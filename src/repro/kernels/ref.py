"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function mirrors one kernel's semantics with straight-line jnp —
no tiling, no scratch, no tricks.  Kernel tests sweep shapes/dtypes and
assert_allclose against these.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search as search_lib
from repro.core.models import dense_f32
from repro.kernels import rmi_lookup as rmi_lookup_lib


def _rmi_predict_flat(
    q: jax.Array, stage0: tuple, leaf_w: jax.Array, leaf_b: jax.Array,
    *, n: int, num_leaves: int,
):
    """Shared stage-0 MLP -> leaf select -> clipped position, on the
    flat (w0, b0, ...) param layout the kernels take."""
    h = q[:, None]
    nl = len(stage0) // 2
    for i in range(nl):
        h = dense_f32(h, stage0[2 * i], stage0[2 * i + 1])
        if i < nl - 1:
            h = jnp.maximum(h, 0.0)
    p0 = h[:, 0]
    leaf = jnp.clip(
        jnp.floor(p0 * (num_leaves / n)).astype(jnp.int32), 0, num_leaves - 1
    )
    pos = jnp.clip(leaf_w[leaf] * q + leaf_b[leaf], 0.0, float(n - 1))
    return leaf, pos


def rmi_lookup_reference(
    q: jax.Array,
    stage0: tuple,
    leaf_w: jax.Array,
    leaf_b: jax.Array,
    err_lo: jax.Array,
    err_hi: jax.Array,
    sorted_keys: jax.Array,
    *,
    n: int,
    num_leaves: int,
) -> jax.Array:
    """Exact lower-bound via full searchsorted, but window-clamped the
    same way the kernel is (predictions outside the window behave
    identically)."""
    leaf, pos = _rmi_predict_flat(
        q, stage0, leaf_w, leaf_b, n=n, num_leaves=num_leaves
    )
    lo = jnp.clip((pos + err_lo[leaf]).astype(jnp.int32), 0, n)
    hi = jnp.clip((pos + err_hi[leaf]).astype(jnp.int32) + 1, 0, n)
    # lower bound within [lo, hi] — oracle via searchsorted then clamp
    full = jnp.searchsorted(sorted_keys, q, side="left").astype(jnp.int32)
    return jnp.clip(full, lo, hi)


def rmi_merged_lookup_reference(
    q: jax.Array,
    stage0: tuple,
    leaf_w: jax.Array,
    leaf_b: jax.Array,
    err_lo: jax.Array,
    err_hi: jax.Array,
    sorted_keys: jax.Array,
    delta_keys: jax.Array,
    delta_prefix: jax.Array,
    *,
    n: int,
    num_leaves: int,
    max_window: int,
) -> tuple:
    """XLA fallback for `rmi_merged_lookup_pallas` — identical signature
    (minus tiling args), identical arithmetic, pure jnp.

    Runs the same stage-0 MLP / leaf FMA / first probe / fixed-trip
    bounded base search and the same full-range delta lower bound, so
    its ``(base_lb, merged_rank)`` is bit-identical to the kernel's for
    *every* query (present, absent, adversarial) — this is the
    correctness contract the parity suite pins both against.
    """
    leaf, pos = _rmi_predict_flat(
        q, stage0, leaf_w, leaf_b, n=n, num_leaves=num_leaves
    )
    base = search_lib.model_binary_search(
        sorted_keys, q, pos, err_lo[leaf], err_hi[leaf], max_window
    )
    dlb = search_lib.lower_bound_full(delta_keys, q)
    return base, base + delta_prefix[dlb]


def rmi_sharded_merged_lookup_reference(
    q: jax.Array,                  # (S, B) per-shard normalized queries
    stage0: tuple,                 # (w0, b0, ...) each stacked (S, ...)
    leaf_w: jax.Array,             # (S, M)
    leaf_b: jax.Array,             # (S, M)
    err_lo: jax.Array,             # (S, M)
    err_hi: jax.Array,             # (S, M)
    sorted_keys: jax.Array,        # (S, N)
    delta_keys: jax.Array,         # (S, D)
    delta_prefix: jax.Array,       # (S, D+1)
    shard_n: jax.Array,            # (S,) int32
    shard_m: jax.Array,            # (S,) int32
    shard_ratio: jax.Array,        # (S,) float32
    *,
    max_window: int,
) -> tuple:
    """XLA fallback for `rmi_sharded_merged_lookup_pallas`: the same
    per-shard body vmapped over the shard axis instead of iterated by
    the kernel grid, so ``(local_base, delta_contrib)`` is bit-identical
    to the kernel's.  Unlike the other oracles here it shares the
    kernel's (pure-jnp) body on purpose — the independent oracle for
    the sharded path is ``np.searchsorted`` in the parity suite, and
    sharing the body is what makes this a drop-in fallback rather than
    a second implementation to keep in sync.
    """
    hidden = tuple(int(w.shape[-1]) for w in stage0[:-2:2])
    steps = rmi_lookup_lib._search_steps(max_window)
    dsteps = rmi_lookup_lib._search_steps(delta_keys.shape[1])

    def one_shard(q_s, params, *rest):
        arrays, (n, m, ratio) = rest[:-3], rest[-3:]
        return rmi_lookup_lib._shard_lookup(
            q_s, lambda off: params[off], hidden,
            *(rmi_lookup_lib._xla(a) for a in arrays), n, m, ratio,
            steps=steps, dsteps=dsteps,
        )

    return jax.vmap(one_shard)(
        q, rmi_lookup_lib._flat_params(stage0), leaf_w, leaf_b, err_lo,
        err_hi, sorted_keys, delta_keys, delta_prefix, shard_n, shard_m,
        shard_ratio,
    )


def rmi_scan_page_reference(
    starts: jax.Array,             # (G,) int32 page start ranks
    base_keys: jax.Array,          # (N,) sorted normalized f32
    base_vals: jax.Array,          # (N,) int32
    ins_keys: jax.Array,           # (Di,) +inf-padded eff. insert keys
    ins_vals: jax.Array,           # (Di,) int32
    del_pos: jax.Array,            # (Dd,) n-padded dead base positions
    end_rank: jax.Array,           # (1,) int32
    *,
    page_size: int,
) -> tuple:
    """XLA fallback for `rmi_scan_page_pallas`: the same
    `_scan_page_body` evaluated on the full (G, page_size) rank matrix
    instead of per kernel grid step, so ``(keys, vals, live)`` is
    bit-identical to the kernel's for every input — including +inf pads
    and out-of-range ranks.  Like the sharded fallback, sharing the
    body is the point: the independent oracle for the scan path is the
    NumPy merge in the test suite.
    """
    steps = rmi_lookup_lib._search_steps(base_keys.shape[0])
    isteps = rmi_lookup_lib._search_steps(ins_keys.shape[0])
    dsteps = rmi_lookup_lib._search_steps(del_pos.shape[0])
    t = starts.astype(jnp.int32)[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1
    )
    flats = [rmi_lookup_lib._xla(a) for a in
             (base_keys, base_vals, ins_keys, ins_vals, del_pos)]
    return rmi_lookup_lib._scan_page_body(
        t, *flats, end_rank[0], steps=steps, isteps=isteps, dsteps=dsteps,
    )


def rmi_scan_range_reference(
    bounds: jax.Array,             # (2,) f32 normalized [lo, hi)
    base_keys: jax.Array,          # (N,) sorted normalized f32
    base_vals: jax.Array,          # (N,) int32
    ins_keys: jax.Array,           # (D,) +inf-padded eff. insert keys
    ins_vals: jax.Array,           # (D,) int32
    ins_rank: jax.Array,           # (D,) i32 merged rank per insert
    dead_pos: jax.Array,           # (T,) i32 sorted dead positions, big pad
    dead_rank: jax.Array,          # (T,) i32 live rows below each, big pad
    *,
    page_size: int,
    max_pages: int,
) -> tuple:
    """XLA fallback for `rmi_scan_range_pallas`: the same endpoint
    ranking (`_merged_rank`) and row resolution (`_scan_rows`)
    evaluated on the full (G, page_size) target matrix, so ``(keys,
    vals, live)`` is bit-identical to the kernel's for every input —
    one fused XLA program, no host ranks.
    """
    steps = rmi_lookup_lib._search_steps(base_keys.shape[0])
    isteps = rmi_lookup_lib._search_steps(ins_keys.shape[0])
    msteps = rmi_lookup_lib._search_steps(ins_rank.shape[0])
    tsteps = rmi_lookup_lib._search_steps(dead_pos.shape[0])
    (base_keys, base_vals, ins_keys, ins_vals, ins_rank, dead_pos,
     dead_rank) = (
        rmi_lookup_lib._xla(a) for a in
        (base_keys, base_vals, ins_keys, ins_vals, ins_rank, dead_pos,
         dead_rank)
    )
    # the scopes (here and in `_scan_rows`) name the program's steps in
    # its operations' metadata
    with jax.named_scope("endpoint_search"):
        r = rmi_lookup_lib._merged_rank(
            bounds, base_keys, dead_pos, ins_keys,
            steps=steps, tsteps=tsteps, isteps=isteps,
        )
    r0 = r[0]
    r1 = jnp.maximum(r[1], r0)
    t = r0 + jax.lax.broadcasted_iota(
        jnp.int32, (max_pages, page_size), 0
    ) * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (max_pages, page_size), 1
    )
    return rmi_lookup_lib._scan_rows(
        t, t < r1, base_keys, base_vals, ins_keys, ins_vals, ins_rank,
        dead_rank, msteps=msteps, tsteps=tsteps,
    )


def rmi_sharded_scan_page_reference(
    base_keys: jax.Array,          # (S, N) sorted f32, +inf padded
    base_vals: jax.Array,          # (S, N) int32
    ins_keys: jax.Array,           # (S, D) +inf padded
    ins_vals: jax.Array,           # (S, D) int32
    ins_rank: jax.Array,           # (S, D) i32, big pad
    dead_rank: jax.Array,          # (S, T) i32, big pad
    ls0: jax.Array,                # (S,) i32
    own_lo: jax.Array,             # (S,) i32
    own_hi: jax.Array,             # (S,) i32
    *,
    page_size: int,
    max_pages: int,
) -> tuple:
    """XLA fallback for `rmi_sharded_scan_page_pallas`: the same
    per-shard `_scan_rows` vmapped over the shard axis instead of
    iterated by the kernel grid — bit-identical (S, G, P) matrices,
    same owner-mask emission."""
    msteps = rmi_lookup_lib._search_steps(ins_rank.shape[1])
    tsteps = rmi_lookup_lib._search_steps(dead_rank.shape[1])
    t_rel = jax.lax.broadcasted_iota(
        jnp.int32, (max_pages, page_size), 0
    ) * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (max_pages, page_size), 1
    )

    def one_shard(base, bvals, ins, ivals, irank, drank, l0, olo, ohi):
        owner = (t_rel >= olo) & (t_rel < ohi)
        t_local = l0 + t_rel - olo
        return rmi_lookup_lib._scan_rows(
            t_local, owner,
            *(rmi_lookup_lib._xla(a)
              for a in (base, bvals, ins, ivals, irank, drank)),
            msteps=msteps, tsteps=tsteps,
        )

    return jax.vmap(one_shard)(
        base_keys, base_vals, ins_keys, ins_vals, ins_rank, dead_rank,
        ls0, own_lo, own_hi,
    )


def bloom_probe_reference(
    queries_u32: jax.Array, words: jax.Array, *, num_bits: int, k: int
) -> jax.Array:
    def mix(h, seed):
        h = h ^ jnp.uint32(seed * 0x9E3779B9 & 0xFFFFFFFF)
        h ^= h >> 16
        h *= jnp.uint32(0x7FEB352D)
        h ^= h >> 15
        h *= jnp.uint32(0x846CA68B)
        h ^= h >> 16
        return h

    q = queries_u32.astype(jnp.uint32)
    h1, h2 = mix(q, 1), mix(q, 2) | jnp.uint32(1)
    hit = jnp.ones(q.shape, bool)
    for i in range(k):
        bit = (h1 + jnp.uint32(i) * h2) % jnp.uint32(num_bits)
        hit &= (words[(bit >> 5).astype(jnp.int32)] & (jnp.uint32(1) << (bit & jnp.uint32(31)))) != 0
    return hit


def hash_probe_reference(
    q, s0_w, s0_b, leaf_w, leaf_b, slot_key, slot_next, ovf_key, ovf_next,
    *, n: int, num_leaves: int, num_slots: int,
) -> jax.Array:
    p0 = q * s0_w[0, 0] + s0_b[0]
    leaf = jnp.clip(
        jnp.floor(p0 * (num_leaves / n)).astype(jnp.int32), 0, num_leaves - 1
    )
    pos = jnp.clip(leaf_w[leaf] * q + leaf_b[leaf], 0.0, float(n - 1))
    slot = jnp.clip(
        (pos * jnp.float32(num_slots / n)).astype(jnp.int32), 0, num_slots - 1
    )
    found = slot_key[slot] == q
    nxt = slot_next[slot]
    # walk chains to exhaustion (python loop over max possible)
    for _ in range(int(ovf_key.shape[0]) + 1):
        valid = nxt >= 0
        if not bool(jnp.any(valid)):
            break
        safe = jnp.maximum(nxt, 0)
        found = found | (valid & (ovf_key[safe] == q))
        nxt = jnp.where(valid, ovf_next[safe], -1)
    return found


def mha_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True
) -> jax.Array:
    """(B, Hq, S, D) GQA attention, fp32 softmax, no tiling."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s_ = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32)
    ) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        s_ = jnp.where(mask[None, None], s_, -1e30)
    p = jax.nn.softmax(s_, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32)).astype(q.dtype)
