"""Host-side exact reranking (paper §IV-A2, step 4).

PIMCQG evicts raw vectors from PIM; the PUs return over-fetched approximate
candidate sets (EF per lane) and the host recomputes exact distances for the
union and takes the final top-k. This is stage 5 of the async pipeline and —
per the paper's own breakdown (Fig 14) — the dominant stage, which is why it
must overlap with in-PIM search (core/pipeline.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kernel_ops

__all__ = ["RerankResult", "rerank"]


class RerankResult(NamedTuple):
    ids: jax.Array    # (Q, k) int32 global ids, -1 pad
    dists: jax.Array  # (Q, k) f32 exact squared distances


@functools.partial(jax.jit, static_argnames=("k",))
def rerank(queries: jax.Array, cand_ids: jax.Array, vectors: jax.Array,
           *, k: int) -> RerankResult:
    """Exact rerank.

    queries (Q, D) f32; cand_ids (Q, C) int32 global ids (-1 = pad, duplicates
    allowed — deduped here); vectors (N, D) f32 host store.
    """
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)        # (Q, 1)
    safe = jnp.clip(cand_ids, 0)
    cand = vectors[safe]                                           # (Q, C, D)
    c2 = jnp.sum(cand * cand, axis=-1)                             # (Q, C)
    # exact distances: on a TPU the default precision would round the f32
    # operands of this contraction to bfloat16
    dots = jnp.einsum("qd,qcd->qc", queries, cand,
                      precision=jax.lax.Precision.HIGHEST)
    d2 = q2 + c2 - 2.0 * dots

    # dedup (keep-first) + k-selection, dispatched Pallas-vs-ref through the
    # kernel seam: the ref is one stable argsort + a flag scatter + lax.top_k
    # (O(C log C), memory-linear — never a (Q, C, C) XLA intermediate); the
    # kernel fuses both into a streaming partial-bitonic selection. The two
    # are bitwise-identical (tests/test_topk_select.py).
    ids, dists = kernel_ops.topk_select(cand_ids, d2, k=k)
    return RerankResult(ids, dists)
