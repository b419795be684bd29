"""Pallas streaming k-selection kernels for the host-side merge hot path.

Two kernels over per-query candidate rows (paper Fig 14: host rerank/merge
is the dominant pipeline stage, and after the sharded tier it sits on the
critical path of every query):

  * ``topk_select`` — fused dedup + bitonic top-k for the origin rerank
    (core/rerank.py). Replaces the pure-XLA stable-argsort dedup +
    ``lax.top_k`` over C = nprobe*ef candidates: a column is flagged as a
    duplicate when an earlier column holds the same id (one lane rotation
    and compare per offset, VMEM-resident, never a (Q, C, C)
    intermediate), then the row is bitonic-sorted and the first k columns
    are kept.

  * ``merge_topk`` — the gather/merge stage of the sharded tier
    (core/topology.py ShardedSink / mesh search_scattered): O per-shard
    partial top-k runs per query, ids disjoint across runs. No dedup, the
    same sort.

Every compare-exchange orders by the (dist, original column) lexicographic
key — a strict total order, so the non-stable bitonic network still has a
unique fixed output and ties resolve to the lower column exactly like
``lax.top_k``. Both kernels are bitwise-identical to kernels/ref.py
(pinned in tests/test_topk_select.py, incl. pads, duplicates and ties).

TPU layout: every value is a 2-D (BQ, W) tile, W a power of two >= 128
lanes and BQ a multiple of 8 sublanes. A compare-exchange partner
``i ^ p`` is one of two lane rotations (``pltpu.roll``), picked per lane
by bit p of the lane index, so the network needs no gather, reshape or
reversal (Mosaic lowers none of these on lane axes). CPU tests run the
same kernels with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["topk_select", "merge_topk", "bitonic_sort", "bitonic_merge",
           "lane_width"]

_LANES = 128
_MAX_BLOCK_ELEMS = 1 << 15   # BQ * W per grid step: ~128 KB per operand


def lane_width(c: int) -> int:
    """Power-of-two lane width >= c and >= one 128-lane vreg row."""
    w = _LANES
    while w < c:
        w *= 2
    return w


def _klt(d1, p1, d2, p2):
    """Strict 'key less-than' on the (key, tie) lexicographic order."""
    return (d1 < d2) | ((d1 == d2) & (p1 < p2))


def _exchange(arrs, col, p: int, up):
    """One compare-exchange stage at stride p over (R, W) lanes. arrs[0:2]
    are the (key, tie) sort key, the rest ride along. Lane i pairs with
    i ^ p; where ``up`` the pair is ordered ascending, else descending."""
    w = col.shape[-1]
    low = (col & p) == 0
    other = [jnp.where(low, pltpu.roll(a, w - p, 1), pltpu.roll(a, p, 1))
             for a in arrs]
    other_first = _klt(other[0], other[1], arrs[0], arrs[1])
    # the low lane of an ascending pair keeps the smaller key, the high
    # lane the larger; a descending pair the other way round (xor, not a
    # select: Mosaic cannot select between boolean vectors)
    take = low ^ up ^ other_first
    return tuple(jnp.where(take, o, a) for o, a in zip(other, arrs))


def bitonic_sort(*arrs, descending: bool = False):
    """Sort every row of (R, W) lanes by (arrs[0], arrs[1]); W a power of
    two. Further arrays are permuted alongside."""
    col = jax.lax.broadcasted_iota(jnp.int32, arrs[0].shape, 1)
    w = col.shape[-1]
    size = 2
    while size <= w:
        up = ((col & size) == 0) ^ descending
        p = size // 2
        while p >= 1:
            arrs = _exchange(arrs, col, p, up)
            p //= 2
        size *= 2
    return arrs


def bitonic_merge(*arrs):
    """Sort rows that are already bitonic (an ascending run followed by a
    descending one) ascending by (arrs[0], arrs[1])."""
    col = jax.lax.broadcasted_iota(jnp.int32, arrs[0].shape, 1)
    p = col.shape[-1] // 2
    while p >= 1:
        arrs = _exchange(arrs, col, p, True)
        p //= 2
    return arrs


def _select_kernel(ids_ref, d_ref, ids_out, d_out, *, k: int, c: int,
                   dedup: bool):
    ids = ids_ref[...]                                     # (BQ, W) i32
    d = d_ref[...]                                         # (BQ, W) f32
    col = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 1)
    if dedup:
        # col i is a duplicate iff an EARLIER col holds the same id (keep-
        # first, matching the ref's stable-sort dedup): after s rotations
        # lane i sees col i - s, which is earlier iff i >= s
        def body(s, carry):
            rolled, dup = carry
            rolled = pltpu.roll(rolled, 1, 1)
            return rolled, jnp.where((rolled == ids) & (col >= s), 1, dup)
        _, dup = jax.lax.fori_loop(1, c, body, (ids, jnp.zeros_like(ids)))
        d = jnp.where((ids < 0) | (dup == 1), jnp.inf, d)
    # pad lanes (col >= c) hold inf and sort after every real column
    d, _, ids = bitonic_sort(d, col, ids)
    out_d = d[:, :k]
    ids_out[...] = jnp.where(jnp.isfinite(out_d), ids[:, :k], -1)
    d_out[...] = out_d


def _select(ids: jax.Array, d: jax.Array, *, k: int, dedup: bool,
            interpret: bool) -> tuple[jax.Array, jax.Array]:
    q, c = ids.shape
    assert d.shape == (q, c), (d.shape, (q, c))
    assert k <= c, (k, c)
    w = lane_width(c)
    bq = 8 * max(1, min(-(-q // 8), _MAX_BLOCK_ELEMS // (8 * w)))
    qp = -(-q // bq) * bq
    ids = jnp.pad(ids.astype(jnp.int32), ((0, qp - q), (0, w - c)),
                  constant_values=-1)
    d = jnp.pad(d.astype(jnp.float32), ((0, qp - q), (0, w - c)),
                constant_values=jnp.inf)
    kernel = functools.partial(_select_kernel, k=k, c=c, dedup=dedup)
    out_ids, out_d = pl.pallas_call(
        kernel,
        grid=(qp // bq,),
        in_specs=[pl.BlockSpec((bq, w), lambda i: (i, 0)),
                  pl.BlockSpec((bq, w), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bq, k), lambda i: (i, 0)),
                   pl.BlockSpec((bq, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((qp, k), jnp.int32),
                   jax.ShapeDtypeStruct((qp, k), jnp.float32)],
        interpret=interpret,
    )(ids, d)
    return out_ids[:q], out_d[:q]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select(cand_ids: jax.Array, dists: jax.Array, *, k: int,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Fused dedup + top-k. Semantics: kernels/ref.py topk_select_ref."""
    return _select(cand_ids, dists, k=k, dedup=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def merge_topk(part_ids: jax.Array, part_dists: jax.Array, *, k: int,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Merge O per-shard partial top-k runs per query (no dedup: the
    cluster partition makes ids disjoint across runs). Semantics:
    kernels/ref.py merge_topk_ref."""
    return _select(part_ids, part_dists, k=k, dedup=False,
                   interpret=interpret)
