"""In-PU greedy beam search (paper §II-A, Fig 2) over the compact index.

One *lane* = one (query, probed-cluster) pair, executing entirely inside the
shard that owns the cluster — PIMCQG's O1 guarantees traversal never crosses
the shard boundary. The search maintains a single beam of size EF (the
over-fetched candidate set, §IV-A2); the host reranks lanes afterwards.

Static-shape, jit-compatible: fixed beam EF, fixed iteration cap, dense
visited bitmap over the padded cluster budget M. Batched with vmap over
lanes; distributed with shard_map in core/engine.py.

ONE traversal skeleton, parameterized by a ``RankingBackend``
(core/backends.py): the backend supplies the candidate-ranking kernel, its
rank dtype, and its pad/sentinel value. Both entry points take the same
three runtime arguments —

    shard : the vmapped single-shard view of ``engine.PlacedIndex``
            (whole cluster stacks; lanes index them lazily so vmap never
            materializes per-lane (M, ...) slices — the §Perf P2 pathology)
    cl    : () i32 clipped local cluster id of this lane
    lane  : the backend's per-lane LUT pytree (one row of ``prepare_lanes``)

plus static (backend, cfg: LaneConfig).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .backends import LaneConfig, RankingBackend

__all__ = ["BeamResult", "beam_search_lane", "full_scan_lane"]


class BeamResult(NamedTuple):
    ids: jax.Array    # (EF,) int32 local node ids, -1 pad
    rank: jax.Array   # (EF,) rank values (backend.rank_dtype), pad = +max
    hops: jax.Array   # () int32 — expansions performed (paper Fig 19 uses this)


@functools.partial(jax.jit, static_argnames=("backend", "cfg"))
def beam_search_lane(shard, cl: jax.Array, lane, *,
                     backend: RankingBackend, cfg: LaneConfig) -> BeamResult:
    """Greedy beam search of one lane over cluster ``cl``."""
    m, r_deg = shard.neighbors.shape[-2:]
    pad_rank = backend.pad_rank
    entry = shard.entry[cl]

    def rank_ids(ids):
        return backend.rank_ids(shard, cl, ids, lane, cfg.dim)

    beam_ids = jnp.full((cfg.ef,), -1, jnp.int32).at[0].set(entry)
    beam_rank = jnp.full((cfg.ef,), pad_rank, backend.rank_dtype).at[0].set(
        rank_ids(entry[None])[0])
    expanded = jnp.zeros((cfg.ef,), bool)
    visited = jnp.zeros((m,), bool).at[entry].set(True)

    def cond(state):
        i, _, beam_rank, expanded, _ = state
        frontier = jnp.where(expanded, pad_rank, beam_rank)
        return (i < cfg.max_iters) & (jnp.min(frontier) < pad_rank)

    # each hop's ops sit under one scope: select, expand, visited, rank
    def body(state):
        i, beam_ids, beam_rank, expanded, visited = state
        with jax.named_scope("select"):
            # pick the best unexpanded beam entry
            frontier = jnp.where(expanded, pad_rank, beam_rank)
            sel = jnp.argmin(frontier)
            expanded = expanded.at[sel].set(True)
            node = beam_ids[sel]

        with jax.named_scope("expand"):
            nbrs = shard.neighbors[cl, jnp.clip(node, 0)]       # (R,)
        with jax.named_scope("visited"):
            fresh = (nbrs >= 0) & ~visited[jnp.clip(nbrs, 0)] & (node >= 0)
            nbrs = jnp.where(fresh, nbrs, -1)
            visited = visited.at[jnp.clip(nbrs, 0)].set(
                visited[jnp.clip(nbrs, 0)] | (nbrs >= 0))
        with jax.named_scope("rank"):
            nrank = rank_ids(nbrs)                              # (R,)

        with jax.named_scope("select"):
            # merge beam + neighbors, keep the best EF by ascending rank
            # (EF+R is tiny)
            all_ids = jnp.concatenate([beam_ids, nbrs])
            all_rank = jnp.concatenate([beam_rank, nrank])
            all_exp = jnp.concatenate([expanded, jnp.zeros((r_deg,), bool)])
            take = jnp.argsort(all_rank)[:cfg.ef]
        return (i + 1, all_ids[take], all_rank[take], all_exp[take], visited)

    state = (jnp.int32(0), beam_ids, beam_rank, expanded, visited)
    hops, beam_ids, beam_rank, _, _ = jax.lax.while_loop(cond, body, state)
    return BeamResult(beam_ids, beam_rank, hops)


@functools.partial(jax.jit, static_argnames=("backend", "cfg"))
def full_scan_lane(shard, cl: jax.Array, lane, *,
                   backend: RankingBackend, cfg: LaneConfig) -> BeamResult:
    """GEMV-mode scan of the whole cluster (paper §V-E2 projects PIMCQG onto
    PIM-HBM/AiM with exactly this kernel shape) — also the oracle that bounds
    what beam search can find inside a cluster."""
    m = shard.codes.shape[-2]
    node_valid = jnp.arange(m) < shard.n_valid[cl]
    r = backend.rank_cluster(shard, cl, lane, cfg.dim)          # (M,)
    r = jnp.where(node_valid, r, backend.pad_rank)
    neg, ids = jax.lax.top_k(-r, cfg.ef)
    return BeamResult(ids.astype(jnp.int32), -neg, jnp.int32(m))
