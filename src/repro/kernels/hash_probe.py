"""Hash-Model probe Pallas kernel (paper §4): CDF-hash + slot compare.

Computes h(K) = F(K)·M with the RMI's linear stage-0 + leaf FMA (the
hash-model configuration the paper benchmarks has no hidden layers),
then compares the primary slot and walks the chained overflow with a
fixed trip count — all VMEM-resident gathers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _hash_kernel(
    q_ref, s0w_ref, s0b_ref, leaf_w_ref, leaf_b_ref,
    slot_key_ref, slot_next_ref, ovf_key_ref, ovf_next_ref, out_ref,
    *, n: int, num_leaves: int, num_slots: int, trips: int,
):
    q = q_ref[...]
    # linear stage-0
    p0 = q * s0w_ref[0, 0] + s0b_ref[0]
    leaf = jnp.clip(
        jnp.floor(p0 * (num_leaves / n)).astype(jnp.int32), 0, num_leaves - 1
    )
    pos = jnp.take(leaf_w_ref[...], leaf) * q + jnp.take(leaf_b_ref[...], leaf)
    pos = jnp.clip(pos, 0.0, float(n - 1))
    # ONE f32 multiply by a shared precomputed constant: bitwise
    # identical across build (numpy), reference (jnp) and this kernel
    slot = jnp.clip(
        (pos * jnp.float32(num_slots / n)).astype(jnp.int32), 0, num_slots - 1
    )

    found = jnp.take(slot_key_ref[...], slot) == q
    nxt = jnp.take(slot_next_ref[...], slot)
    for _ in range(trips):
        valid = nxt >= 0
        safe = jnp.maximum(nxt, 0)
        found = found | (valid & (jnp.take(ovf_key_ref[...], safe) == q))
        nxt = jnp.where(valid, jnp.take(ovf_next_ref[...], safe), -1)
    out_ref[...] = found


@functools.partial(
    jax.jit,
    static_argnames=("n", "num_leaves", "num_slots", "trips", "block_q", "interpret"),
)
def hash_probe_pallas(
    q: jax.Array,            # (B,) normalized query keys
    s0_w: jax.Array,         # (1, 1) linear stage-0 weight
    s0_b: jax.Array,         # (1,)
    leaf_w: jax.Array,       # (M,)
    leaf_b: jax.Array,       # (M,)
    slot_key: jax.Array,     # (S,) normalized stored keys (NaN = empty)
    slot_next: jax.Array,    # (S,) int32
    ovf_key: jax.Array,      # (O,)
    ovf_next: jax.Array,     # (O,) int32
    *,
    n: int,
    num_leaves: int,
    num_slots: int,
    trips: int,
    block_q: int = 2048,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b = q.shape[0]
    bq = min(block_q, b)
    padded = (b + bq - 1) // bq * bq
    if padded != b:
        q = jnp.pad(q, (0, padded - b))
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)
    out = pl.pallas_call(
        functools.partial(
            _hash_kernel, n=n, num_leaves=num_leaves,
            num_slots=num_slots, trips=trips,
        ),
        grid=(padded // bq,),
        in_specs=[pl.BlockSpec((bq,), lambda i: (i,))]
        + [full(a) for a in (s0_w, s0_b, leaf_w, leaf_b, slot_key,
                             slot_next, ovf_key, ovf_next)],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), jnp.bool_),
        interpret=resolve_interpret(interpret),
    )(q, s0_w, s0_b, leaf_w, leaf_b, slot_key, slot_next, ovf_key, ovf_next)
    return out[:b]
