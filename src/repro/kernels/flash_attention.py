"""Blocked causal flash attention (GQA) — the LM substrate's hot spot.

Online-softmax attention with BlockSpec tiling: the (S×S) score matrix
is never materialized; VMEM holds one (blk_q × blk_k) tile plus running
(max, sum, acc) scratch.  MXU-aligned block sizes (multiples of 128).
GQA is expressed in the index_map: the kv block index is the query-head
index divided by the group size — no materialized head repetition.

Fully-masked causal tiles are skipped via pl.when (≈2× fewer tiles).
Validated in interpret mode against ref.mha_reference.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, causal: bool, blk_q: int, blk_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = True
    if causal:
        # skip tiles strictly above the diagonal
        run = ki * blk_k <= qi * blk_q + blk_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (blk_q, blk_k)
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                       # (blk_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)           # (blk_q, 1)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "blk_q", "blk_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    causal: bool = True,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    group = hq // hkv
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, s)
    assert s % blk_q == 0 and s % blk_k == 0, "seq must divide block size"
    scale = 1.0 / math.sqrt(d)
    grid = (b, hq, s // blk_q, s // blk_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k
    )
    scratch = [
        pltpu.VMEM((blk_q, d), jnp.float32),
        pltpu.VMEM((blk_q, 1), jnp.float32),
        pltpu.VMEM((blk_q, 1), jnp.float32),
    ]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, blk_k, d),
                lambda bb, h, qi, ki, g=group: (bb, h // g, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, blk_k, d),
                lambda bb, h, qi, ki, g=group: (bb, h // g, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, blk_q, d), lambda bb, h, qi, ki: (bb, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(q, k, v)
