"""Bloom-filter probe Pallas kernel (paper §5 baseline op).

The bit array lives in VMEM as uint32 words (a 1.76 GB paper-scale
filter shards to ~7 MB/chip on a 256-chip pod); k probes per query are
vector shifts/masks + one VMEM gather each — no branches.  Queries are
pre-folded to uint32 on the host (strings: FNV; ints: mix64 fold).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _mix32(h, seed: int):
    h = h ^ jnp.uint32(seed * 0x9E3779B9 & 0xFFFFFFFF)
    h ^= h >> 16
    h *= jnp.uint32(0x7FEB352D)
    h ^= h >> 15
    h *= jnp.uint32(0x846CA68B)
    h ^= h >> 16
    return h


def _bloom_kernel(q_ref, words_ref, out_ref, *, num_bits: int, k: int):
    q = q_ref[...].astype(jnp.uint32)
    words = words_ref[...]
    h1 = _mix32(q, 1)
    h2 = _mix32(q, 2) | jnp.uint32(1)
    hit = jnp.ones(q.shape, jnp.bool_)
    for i in range(k):
        bit = (h1 + jnp.uint32(i) * h2) % jnp.uint32(num_bits)
        word = (bit >> 5).astype(jnp.int32)
        mask = jnp.uint32(1) << (bit & jnp.uint32(31))
        hit &= (jnp.take(words, word) & mask) != 0
    out_ref[...] = hit


@functools.partial(
    jax.jit, static_argnames=("num_bits", "k", "block_q", "interpret")
)
def bloom_probe_pallas(
    queries_u32: jax.Array,   # (B,) uint32 pre-folded keys
    words: jax.Array,         # (num_bits/32,) uint32
    *,
    num_bits: int,
    k: int,
    block_q: int = 2048,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b = queries_u32.shape[0]
    bq = min(block_q, b)
    padded = (b + bq - 1) // bq * bq
    if padded != b:
        queries_u32 = jnp.pad(queries_u32, (0, padded - b))
    out = pl.pallas_call(
        functools.partial(_bloom_kernel, num_bits=num_bits, k=k),
        grid=(padded // bq,),
        in_specs=[
            pl.BlockSpec((bq,), lambda i: (i,)),
            pl.BlockSpec(words.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bq,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), jnp.bool_),
        interpret=resolve_interpret(interpret),
    )(queries_u32, words)
    return out[:b]
