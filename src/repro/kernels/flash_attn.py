"""Pallas TPU flash-attention FORWARD kernel (roadmap item from §Perf).

The XLA-level blockwise scan in models/attention.py is numerically
identical but materializes (bq, bk) score tiles in HBM between fused ops;
this kernel keeps them in VMEM. Grid = (batch·kv-heads, Sq/BQ): each step
owns one (BQ, dk) query tile for one (batch, kv-head) lane (GQA group
folded into BQ's head of the q tile caller-side), loops KV chunks with a
fori_loop carrying the online-softmax (m, l, acc) in registers/VMEM.

VMEM budget per step (defaults BQ=256, BK=512, dk≤256):
q 256·256·4 = 256 KB, k/v chunk 512·256·4·2 = 1 MB, scores 256·512·4 =
512 KB, acc 256·256·4 = 256 KB → ~2 MB, double-bufferable.

Backward falls back to the custom-VJP scan (models/attention.py) — the
flash backward kernel is scoped, not yet written. Forward is validated
against kernels/ref-style oracles in interpret mode
(tests/test_flash_kernel.py) over shape/dtype/causality sweeps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, causal: bool,
                      scale: float, q_offset: int):
    """One grid step: (BQ, dk) queries vs all KV of this (batch, head)."""
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # (BQ, dk)
    bq, dk = q.shape
    skv = k_ref.shape[1]
    nkv = skv // bk

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # (BK, dk)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)  # (BK, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kv_pos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc = acc * corr[:, None] + pv
        return m_new, l, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nkv, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "q_offset", "interpret"))
def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, bq: int = 256, bk: int = 512,
                        q_offset: int = 0, interpret: bool = False
                        ) -> jax.Array:
    """q (BH, Sq, dk), k/v (BH, Sk, dk/dv) — heads pre-folded into BH
    (GQA: repeat kv lanes caller-side). Returns (BH, Sq, dv) in q.dtype."""
    bh, sq, dk = q.shape
    skv, dv = k.shape[1], v.shape[-1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    scale = 1.0 / math.sqrt(dk)
    kernel = functools.partial(_flash_fwd_kernel, bk=bk, causal=causal,
                               scale=scale, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, skv, dk), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, skv, dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        interpret=interpret,
    )(q, k, v)
