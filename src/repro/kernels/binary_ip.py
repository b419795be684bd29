"""Pallas TPU kernels for the PIMCQG PU-side search engine.

Two kernels:

  * ``binary_ip_rank``  — rank a block of candidate nodes: the LUT sum
    S = <bits, lut> over the 1-bit RabitQ codes, then the O3 integer
    epilogue (t = 2S - sumq; shift-add 1/alpha; rank = f_add - t'). This is
    the TPU-native reformulation of the paper's bit-serial DPU loop
    (DESIGN.md §2): one lane per node, all nodes of a block at once,
    instead of per-neighbor pointer chasing.

  * ``cluster_scan``    — the GEMV-mode engine (paper §V-E2): fused
    whole-cluster rank + running top-EF across the grid, one VMEM-resident
    scratch beam, only (EF,) results ever leave the core.

TPU layout: nodes run along lanes. The wrapper hands the kernel the codes
transposed, (W, N) uint8, so a (W, BN) block puts one node per lane and
its W code bytes down the sublanes; f_add and the ranks are (1, N) rows;
the LUT arrives as (W, 8) (byte w, bit j -> lut[8w + j]); the scalars sit
in SMEM. The LUT sum is then eight shift-and-mask passes and one sublane
reduction, all int32 — exact, like the ref. Every block is lane-dense
(BN a multiple of 128), so no 1-D operand has to match an XLA tiling.

``cluster_scan`` keeps the running top-EF as (rank, node id) lanes sorted
ascending: each block is bitonic-sorted descending, its smallest E lanes
joined after the running run make one bitonic sequence, and a bitonic
merge keeps the lower E. Ties break on the lower node id, as in the ref.
CPU tests run the same kernels with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .topk_select import bitonic_merge, bitonic_sort, lane_width

INT_MAX = jnp.iinfo(jnp.int32).max

BLOCK_N = 512  # nodes per grid step


def _lut_sum(codes_t: jax.Array, lut8: jax.Array) -> jax.Array:
    """(W, BN) uint8 codes, one node per lane, and the (W, 8) LUT ->
    (1, BN) int32 S = sum over bits of bit * lut (little-endian bytes)."""
    c = codes_t.astype(jnp.int32)
    s = jnp.zeros(c.shape, jnp.int32)
    for j in range(8):
        s = s + ((c >> j) & 1) * lut8[:, j:j + 1]
    return jnp.sum(s, axis=0, keepdims=True)


def _epilogue(s: jax.Array, f_add: jax.Array, sumq: jax.Array,
              s1: jax.Array, s2: jax.Array) -> jax.Array:
    """O3 integer epilogue: (1, BN) S and f_add -> (1, BN) i32 rank."""
    t = 2 * s - sumq
    tp = t + (t >> s1) + jnp.where(s2 >= 31, 0, t >> jnp.minimum(s2, 30))
    return f_add - tp


def _rank_block(scal_ref, codes_ref, f_add_ref, lut_ref) -> jax.Array:
    s = _lut_sum(codes_ref[...], lut_ref[...])
    return _epilogue(s, f_add_ref[...], scal_ref[0], scal_ref[1],
                     scal_ref[2])


def _lane_major(codes: jax.Array, f_add: jax.Array, lut: jax.Array,
                bn: int):
    """Pad N to a multiple of bn and lay the operands out lane-major."""
    n, w = codes.shape
    assert lut.shape[0] == w * 8, (lut.shape, w * 8)
    n_pad = -(-n // bn) * bn - n
    codes_t = jnp.pad(codes, ((0, n_pad), (0, 0))).T            # (W, Np)
    f_add = jnp.pad(f_add.astype(jnp.int32), (0, n_pad),
                    constant_values=INT_MAX)[None, :]           # (1, Np)
    lut8 = lut.astype(jnp.int32).reshape(w, 8)
    return codes_t, f_add, lut8


def _block_nodes(n: int, block_n: int) -> int:
    """Nodes per grid step: a power of two >= 128 lanes, at most block_n
    unless one 128-lane row is already more."""
    return min(max(block_n, 128), lane_width(n))


# ---------------------------------------------------------------------------
# Kernel 1: binary_ip_rank
# ---------------------------------------------------------------------------

def _binary_ip_kernel(scal_ref, codes_ref, f_add_ref, lut_ref, out_ref):
    """Grid step: rank one BN-node block. scal_ref (3,) i32 SMEM [sumq, s1,
    s2] | codes_ref (W, BN) u8 | f_add_ref (1, BN) i32 | lut_ref (W, 8) i32
    -> out (1, BN) i32."""
    out_ref[...] = _rank_block(scal_ref, codes_ref, f_add_ref, lut_ref)


@functools.partial(jax.jit, static_argnames=("dim", "interpret", "block_n"))
def binary_ip_rank(codes: jax.Array, f_add: jax.Array, lut: jax.Array,
                   sumq: jax.Array, s1: jax.Array, s2: jax.Array,
                   *, dim: int, interpret: bool = False,
                   block_n: int = BLOCK_N) -> jax.Array:
    """Rank N nodes. codes (N, W) u8, f_add (N,) i32, lut (W*8,) i32 -> (N,) i32."""
    n, w = codes.shape
    bn = _block_nodes(n, block_n)
    codes_t, f_add, lut8 = _lane_major(codes, f_add, lut, bn)
    np_ = codes_t.shape[1]
    scal = jnp.stack([sumq.astype(jnp.int32), s1.astype(jnp.int32),
                      s2.astype(jnp.int32)])
    out = pl.pallas_call(
        _binary_ip_kernel,
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # scalars
            pl.BlockSpec((w, bn), lambda i: (0, i)),        # codes tile
            pl.BlockSpec((1, bn), lambda i: (0, i)),        # f_add tile
            pl.BlockSpec((w, 8), lambda i: (0, 0)),         # lut, replicated
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.int32),
        interpret=interpret,
    )(scal, codes_t, f_add, lut8)
    return out[0, :n]


# ---------------------------------------------------------------------------
# Kernel 2: cluster_scan (fused rank + running top-EF)
# ---------------------------------------------------------------------------

def _cluster_scan_kernel(scal_ref, codes_ref, f_add_ref, lut_ref,
                         ids_out, rank_out, best_rank, best_id, *, bn: int,
                         e: int):
    """Sequential grid; scratch (best_rank/best_id, (1, E) VMEM) persists
    across steps and holds the running top-E ascending by (rank, id);
    results written on the last step."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        # (INT_MAX, INT_MAX) sorts after every real (rank, node id) key
        best_rank[...] = jnp.full((1, e), INT_MAX, jnp.int32)
        best_id[...] = jnp.full((1, e), INT_MAX, jnp.int32)

    r = _rank_block(scal_ref, codes_ref, f_add_ref, lut_ref)   # (1, BN)
    gids = i * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    r = jnp.where(gids < scal_ref[3], r, INT_MAX)
    # block descending: its last E lanes are its smallest E, descending,
    # so running (ascending) ++ those is one bitonic sequence
    r, gids = bitonic_sort(r, gids, descending=True)
    r = jnp.concatenate([best_rank[...], r[:, bn - e:]], axis=1)
    gids = jnp.concatenate([best_id[...], gids[:, bn - e:]], axis=1)
    r, gids = bitonic_merge(r, gids)
    best_rank[...] = r[:, :e]
    best_id[...] = gids[:, :e]

    @pl.when(i == pl.num_programs(0) - 1)
    def _emit():
        ids_out[...] = best_id[...]
        rank_out[...] = best_rank[...]


@functools.partial(jax.jit, static_argnames=("dim", "ef", "interpret", "block_n"))
def cluster_scan(codes: jax.Array, f_add: jax.Array, lut: jax.Array,
                 sumq: jax.Array, s1: jax.Array, s2: jax.Array,
                 n_valid: jax.Array, *, dim: int, ef: int,
                 interpret: bool = False, block_n: int = BLOCK_N
                 ) -> tuple[jax.Array, jax.Array]:
    """Whole-cluster GEMV-mode search: -> (ids (EF,) i32, ranks (EF,) i32)."""
    n, w = codes.shape
    e = lane_width(ef)
    bn = max(_block_nodes(n, block_n), e)
    codes_t, f_add, lut8 = _lane_major(codes, f_add, lut, bn)
    np_ = codes_t.shape[1]
    scal = jnp.stack([sumq.astype(jnp.int32), s1.astype(jnp.int32),
                      s2.astype(jnp.int32),
                      jnp.minimum(n_valid.astype(jnp.int32), n)])
    kernel = functools.partial(_cluster_scan_kernel, bn=bn, e=e)
    ids, ranks = pl.pallas_call(
        kernel,
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((w, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((w, 8), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, e), jnp.int32),
            jax.ShapeDtypeStruct((1, e), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, e), jnp.int32),
                        pltpu.VMEM((1, e), jnp.int32)],
        interpret=interpret,
    )(scal, codes_t, f_add, lut8)
    return ids[0, :ef], ranks[0, :ef]
