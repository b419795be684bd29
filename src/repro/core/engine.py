"""PIMCQG engine — end-to-end query path (paper Fig 4).

    host: cluster filter -> per-lane LUT prep -> dispatch
    PU  : beam search over locally-resident compact clusters (shard_map)
    host: gather candidates -> exact rerank -> top-k

TPU mapping (DESIGN.md §2): the ``model`` mesh axis is the PU array — each
shard owns ``clusters_per_shard`` self-contained compact clusters, placed by
core/placement.py. A *lane* is one (query, probed cluster) unit of in-PU
work; lanes are routed to the shard owning their cluster. Raw vectors (the
"host store") never live on the model axis — they are sharded over the
data axis for the rerank stage.

The candidate-ranking variant is a ``RankingBackend`` (core/backends.py)
selected by ``SearchConfig.mode`` (a registry key; "mulfree" / "exact"
keep their historical meaning). ``PlacedIndex`` is a registered pytree:
shared graph arrays plus the active backend's own array slice, flowing
WHOLE through vmap/shard_map — no positional splatting, no dummy arrays
for inactive modes.

The whole path is one jit-able function with static shapes, so it lowers
under the production mesh for the multi-pod dry-run.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import backends as backends_mod
from . import beam_search, compact_index, ivf, placement as placement_mod
from . import rerank as rerank_mod

__all__ = ["SearchConfig", "PlacedIndex", "PIMCQGEngine", "SearchStats",
           "placed_specs"]


@dataclasses.dataclass(frozen=True)
class SearchConfig:
    nprobe: int = 8
    ef: int = 40              # over-fetched candidate set size (EF > n_b)
    k: int = 10
    max_iters: int = 64       # beam-expansion cap per lane
    mode: str = "mulfree"     # RankingBackend registry key ('mulfree' = O3,
                              # 'exact' = SymphonyQG baseline, 'hamming', ...)
    scan: str = "beam"        # 'beam' | 'gemv' (full-cluster scan, Fig 19)
    lane_capacity_factor: float = 2.0  # per-shard lane buffer headroom
    # adaptive early termination (ivf.adaptive_keep_mask): 0.0 = off (the
    # default keeps every search graph bit-identical to fixed effort).
    # With tau > 0, probe j survives while d2_j <= tau * d2_0; the count is
    # floored at adaptive_min_probes and rounded up to the next rung of
    # adaptive_ladder (ascending probe counts, () = any count). Easy
    # queries then search fewer clusters — and on the sharded tier fan out
    # to fewer shards.
    adaptive_tau: float = 0.0
    adaptive_min_probes: int = 1
    adaptive_ladder: tuple = ()

    def __post_init__(self):
        if self.adaptive_tau < 0:
            raise ValueError(
                f"adaptive_tau must be >= 0 (0 disables), got "
                f"{self.adaptive_tau}")
        if self.adaptive_min_probes < 1:
            raise ValueError(
                f"adaptive_min_probes must be >= 1, got "
                f"{self.adaptive_min_probes}")
        ladder = tuple(self.adaptive_ladder)
        object.__setattr__(self, "adaptive_ladder", ladder)
        if any(int(r) != r or r < 1 for r in ladder) or \
                list(ladder) != sorted(set(ladder)):
            raise ValueError(
                f"adaptive_ladder must be strictly-ascending positive "
                f"ints, got {ladder!r}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlacedIndex:
    """Deployment layout: shard-major (S, C/S, ...) cluster stacks.

    Shared graph/code arrays + ``arrays``, the active backend's own
    per-node/per-cluster slice (its registered pytree dataclass). Under
    ``jax.vmap(..., in_axes=0)`` the same class doubles as the single-shard
    view (leading dim (C/S,)) that beam_search/full_scan lanes index lazily.
    """
    centroids: jax.Array   # (S, Cl, D) f32
    codes: jax.Array       # (S, Cl, M, W) u8 — canonical RabitQ sign codes
    neighbors: jax.Array   # (S, Cl, M, R) i32
    entry: jax.Array       # (S, Cl) i32
    n_valid: jax.Array     # (S, Cl) i32
    node_ids: jax.Array    # (S, Cl, M) i32
    arrays: Any            # backend-owned pytree, (S, Cl, ...) leading


class SearchStats(NamedTuple):
    hops: jax.Array        # (S, L) i32 per-lane expansions (-1 pad lanes = 0)
    dropped_lanes: jax.Array  # () i32 — lanes lost to buffer overflow


def _place(idx: compact_index.CompactIndex, pl: placement_mod.Placement,
           backend: backends_mod.RankingBackend) -> PlacedIndex:
    def rs(a):
        a = np.asarray(a)[pl.order]
        return jnp.asarray(a.reshape(pl.n_shards, pl.per_shard, *a.shape[1:]))
    return PlacedIndex(
        centroids=rs(idx.centroids), codes=rs(idx.codes),
        neighbors=rs(idx.neighbors), entry=rs(idx.entry),
        n_valid=rs(idx.n_valid), node_ids=rs(idx.node_ids),
        arrays=jax.tree.map(rs, backend.index_arrays(idx)),
    )


def placed_specs(n_shards: int, clusters_per_shard: int, budget: int,
                 degree: int, dim: int,
                 backend: backends_mod.RankingBackend) -> PlacedIndex:
    """ShapeDtypeStruct stand-ins for the PIM-resident compact index —
    abstract lowering (launch/anns_step.py) builds exactly the tree
    ``_place`` would, including the backend's slice, without 10^9 nodes."""
    f = jax.ShapeDtypeStruct
    lead = (n_shards, clusters_per_shard)
    w = (dim + ((-dim) % 8)) // 8
    return PlacedIndex(
        centroids=f((*lead, dim), jnp.float32),
        codes=f((*lead, budget, w), jnp.uint8),
        neighbors=f((*lead, budget, degree), jnp.int32),
        entry=f(lead, jnp.int32),
        n_valid=f(lead, jnp.int32),
        node_ids=f((*lead, budget), jnp.int32),
        arrays=backend.array_specs(lead, budget, dim),
    )


# ---------------------------------------------------------------------------
# Lane routing (host dispatch): (Q, nprobe) probes -> per-shard lane tables
# ---------------------------------------------------------------------------

def _lane_capacity(nq: int, nprobe: int, n_shards: int, factor: float) -> int:
    """Per-shard lane-buffer size for an nq-query batch (host-side math;
    also tabulated per n_valid so padded executables drop lanes exactly
    like the unpadded executable would).

    ``factor`` is headroom over a shard's even share nq*nprobe/n_shards,
    capped at the nq*nprobe lanes the batch has: a slot past that is never
    filled, yet runs the beam loop like a live one. nq*nprobe is the only
    bound that always holds (``search_probed`` probes may repeat a
    cluster)."""
    even = int(np.ceil(nq * nprobe / n_shards * factor))
    return max(1, min(even, nq * nprobe))


@functools.partial(jax.jit, static_argnames=("n_shards", "capacity"))
def route_lanes(probe_cids: jax.Array, shard_of: jax.Array, local_slot: jax.Array,
                valid_q: jax.Array | None = None,
                capacity_valid: jax.Array | None = None,
                *, n_shards: int, capacity: int):
    """Build static-shape per-shard lane tables.

    probe_cids (Q, P) cluster ids -> for shard s: lane_q (S, L),
    lane_cl (S, L) local cluster slots (-1 pad); plus the inverse map
    (Q, P) -> flat slot into the (S*L,) result array for candidate gather.
    A probe id of -1 marks a hole (a probed cluster owned by a DIFFERENT
    engine in the sharded fleet tier) — its lane is masked exactly like a
    pad query's and never occupies capacity nor counts as dropped.

    valid_q (Q,) bool marks real queries; lanes of pad queries (bucketed
    batches) are routed to a sentinel shard that sorts after every real
    shard, so real lanes land in exactly the slots an unpadded batch would
    give them, and pads never occupy capacity nor count as dropped.

    capacity_valid (traced scalar <= capacity) optionally tightens the
    drop threshold to the capacity an unpadded batch of the real queries
    would get, so overflow drops are also identical under padding.
    """
    q, p = probe_cids.shape
    flat_cid = probe_cids.reshape(-1)                      # (QP,)
    flat_q = jnp.repeat(jnp.arange(q, dtype=jnp.int32), p)
    live = flat_cid >= 0
    lane_shard = shard_of[jnp.clip(flat_cid, 0)]           # (QP,)
    if valid_q is not None:
        live = live & jnp.repeat(valid_q, p)
    lane_shard = jnp.where(live, lane_shard, n_shards)
    order = jnp.argsort(lane_shard, stable=True)
    sh_sorted = lane_shard[order]
    # position within shard = index - first index of that shard
    first = jnp.searchsorted(sh_sorted, jnp.arange(n_shards), side="left")
    pos = jnp.arange(q * p) - first[jnp.clip(sh_sorted, 0, n_shards - 1)]
    real = sh_sorted < n_shards
    cap = capacity if capacity_valid is None \
        else jnp.minimum(capacity, capacity_valid)
    ok = (pos < cap) & real
    dropped = jnp.sum(~ok & real)

    # overflowing lanes get an out-of-bounds destination -> dropped by scatter
    dest = jnp.where(ok, sh_sorted * capacity + pos, n_shards * capacity)
    lane_q = jnp.full((n_shards * capacity,), -1, jnp.int32)
    lane_cl = jnp.full((n_shards * capacity,), -1, jnp.int32)
    src_q = flat_q[order]
    src_cl = local_slot[jnp.clip(flat_cid[order], 0)].astype(jnp.int32)
    lane_q = lane_q.at[dest].set(src_q, mode="drop")
    lane_cl = lane_cl.at[dest].set(src_cl, mode="drop")

    # inverse: original flat probe -> its result slot (or -1 if dropped)
    inv = jnp.full((q * p,), -1, jnp.int32)
    inv = inv.at[order].set(jnp.where(ok, dest, -1))
    return (lane_q.reshape(n_shards, capacity),
            lane_cl.reshape(n_shards, capacity),
            inv.reshape(q, p), dropped.astype(jnp.int32))


# ---------------------------------------------------------------------------
# In-shard search (the "PU program")
# ---------------------------------------------------------------------------

def _make_shard_search(cfg: SearchConfig, dim: int):
    """Returns f(shard: PlacedIndex-view, rotation, queries, lane_q, lane_cl)
    -> (gids (L, EF), rank (L, EF), hops (L,)) for ONE shard. The backend
    is resolved once from the registry; its lane-LUT pytree flows whole
    through the inner vmap."""
    backend = backends_mod.get_backend(cfg.mode)
    lane_cfg = backends_mod.LaneConfig(ef=cfg.ef, max_iters=cfg.max_iters,
                                       dim=dim)
    scan_lane = beam_search.full_scan_lane if cfg.scan == "gemv" \
        else beam_search.beam_search_lane

    def shard_search(shard: PlacedIndex, rotation, queries, lane_q, lane_cl):
        with jax.named_scope("prepare_lanes"):
            safe_q = jnp.clip(lane_q, 0)
            safe_c = jnp.clip(lane_cl, 0)
            lanes = backend.prepare_lanes(
                queries[safe_q], shard.centroids[safe_c], rotation,
                shard.arrays, safe_c, dim)

        def one_lane(cl, lane):
            c = jnp.clip(cl, 0)
            res = scan_lane(shard, c, lane, backend=backend, cfg=lane_cfg)
            live = cl >= 0
            gids = shard.node_ids[c, jnp.clip(res.ids, 0)]
            gids = jnp.where((res.ids >= 0) & live, gids, -1)
            return gids, res.rank, jnp.where(live, res.hops, 0)

        with jax.named_scope("beam_search"):
            return jax.vmap(one_lane)(lane_cl, lanes)

    return shard_search


def _make_probed_search(cfg: SearchConfig, dim: int, bucket: int, p: int,
                        n_shards: int):
    """The search of one bucket-padded batch over given probes (-1 =
    hole), shared by every serving executable: route lanes -> beam search
    per shard -> gather candidates per query -> exact rerank. Returns
    f(placed, shard_of, local_slot, rotation, vectors, queries, probe,
    n_valid) -> (RerankResult, SearchStats); n_valid <= bucket marks the
    real queries — pads are masked out of routing, search, and rerank.
    Each stage runs under its own ``jax.named_scope`` (``core.obs``)."""
    s = n_shards
    capacity = _lane_capacity(bucket, p, s, cfg.lane_capacity_factor)
    # capacity an UNPADDED batch of n real queries would get, tabulated
    # on host so the traced lookup matches the host formula bit-exactly
    cap_table = jnp.asarray(
        [_lane_capacity(n, p, s, cfg.lane_capacity_factor)
         for n in range(bucket + 1)], jnp.int32)
    shard_fn = _make_shard_search(cfg, dim)

    def probed_search(placed: PlacedIndex, shard_of, local_slot, rotation,
                      vectors, queries, probe, n_valid):
        with jax.named_scope("route_lanes"):
            valid = jnp.arange(bucket, dtype=jnp.int32) < n_valid
            cap_valid = cap_table[jnp.clip(n_valid, 0, bucket)]
            lane_q, lane_cl, inv, dropped = route_lanes(
                probe, shard_of, local_slot, valid, cap_valid,
                n_shards=s, capacity=capacity)
        # the whole PlacedIndex pytree maps over its shard axis at once
        gids, rank, hops = jax.vmap(
            shard_fn, in_axes=(0, None, None, 0, 0))(
            placed, rotation, queries, lane_q, lane_cl)
        with jax.named_scope("rerank"):
            # gather candidates back per query via the inverse lane map
            flat_gids = gids.reshape(s * capacity, cfg.ef)
            safe = jnp.clip(inv, 0)                          # (Q, P)
            cand = flat_gids[safe]                           # (Q, P, EF)
            cand = jnp.where((inv >= 0)[..., None], cand, -1)
            cand = cand.reshape(bucket, p * cfg.ef)
            out = rerank_mod.rerank(queries, cand, vectors, k=cfg.k)
            ids = jnp.where(valid[:, None], out.ids, -1)
            dists = jnp.where(valid[:, None], out.dists, jnp.inf)
        stats = SearchStats(hops=hops, dropped_lanes=dropped)
        return rerank_mod.RerankResult(ids, dists), stats

    return probed_search


def _pad_rows(a: np.ndarray, rows: int, fill) -> np.ndarray:
    """Host-side pad of a batch to ``rows`` rows of ``fill``."""
    if len(a) == rows:
        return a
    pad = np.full((rows - len(a), *a.shape[1:]), fill, a.dtype)
    return np.concatenate([a, pad])


def _trim(out: rerank_mod.RerankResult, nq: int, trim: bool
          ) -> rerank_mod.RerankResult:
    if not trim or out.ids.shape[0] == nq:
        return out
    return rerank_mod.RerankResult(out.ids[:nq], out.dists[:nq])


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class PIMCQGEngine:
    """Single-process engine (tests/benchmarks). The mesh-distributed variant
    is produced by launch/anns_step.py building the same functions under
    shard_map."""

    def __init__(self, index: compact_index.CompactIndex,
                 host: compact_index.HostStore,
                 place: placement_mod.Placement,
                 icfg: compact_index.IndexConfig,
                 scfg: SearchConfig,
                 buckets: tuple[int, ...] | None = None):
        self.index = index
        self.host = host
        self.place = place
        self.icfg = icfg
        self.scfg = scfg
        self.backend = backends_mod.get_backend(scfg.mode)
        self.placed = _place(index, place, self.backend)
        self.shard_of = jnp.asarray(place.shard_of)
        self.local_slot = jnp.asarray(place.local_slot)
        self._search_cache: dict = {}
        self.buckets = tuple(sorted(set(buckets))) if buckets else ()

    # -- construction -------------------------------------------------------
    @classmethod
    def build(cls, key, x: np.ndarray, icfg: compact_index.IndexConfig,
              scfg: SearchConfig, *, n_shards: int = 1,
              freq: np.ndarray | None = None, verbose: bool = False,
              buckets: tuple[int, ...] | None = None) -> "PIMCQGEngine":
        idx, host = compact_index.build_compact_index(key, x, icfg, verbose=verbose)
        sizes = np.asarray(idx.n_valid)
        bpc = sizes * compact_index.compact_bytes_per_node(icfg.dim, icfg.degree)
        if freq is None:
            freq = sizes.astype(np.float64)   # popularity ~ size as prior
        pl = placement_mod.greedy_place(freq, bpc, n_shards)
        return cls(idx, host, pl, icfg, scfg, buckets=buckets)

    # -- query path ---------------------------------------------------------
    def _build_search_fn(self, bucket: int):
        """One XLA executable per *bucket* size; n_valid <= bucket marks the
        real queries — pads are masked out of routing, search, and rerank."""
        cfg = self.scfg
        probed_search = _make_probed_search(cfg, self.icfg.dim, bucket,
                                            cfg.nprobe, self.place.n_shards)

        @jax.jit
        def search_step(placed: PlacedIndex, centroids, rotation, vectors,
                        queries, n_valid):
            with jax.named_scope("cluster_filter"):
                probe, pdist = ivf.cluster_filter(queries, centroids,
                                                  nprobe=cfg.nprobe)
                if cfg.adaptive_tau > 0:
                    # adaptive early termination: easy queries keep fewer
                    # probes; masked probes are -1 holes route_lanes skips
                    keep = ivf.adaptive_keep_mask(
                        pdist, tau=cfg.adaptive_tau,
                        min_probes=cfg.adaptive_min_probes,
                        ladder=cfg.adaptive_ladder)
                    probe = jnp.where(keep, probe, -1)
            return probed_search(placed, self.shard_of, self.local_slot,
                                 rotation, vectors, queries, probe, n_valid)

        return search_step

    def _build_probed_fn(self, bucket: int, p: int):
        """Like _build_search_fn but the probed clusters are an INPUT (local
        cluster ids, -1 = hole) instead of being chosen by cluster_filter —
        the partial-search entry point of the sharded fleet tier, where the
        origin host owns probe selection and this engine owns only a
        disjoint cluster slice. One executable per (bucket, P) shape."""
        probed_search = _make_probed_search(self.scfg, self.icfg.dim, bucket,
                                            p, self.place.n_shards)

        @jax.jit
        def probed_step(placed: PlacedIndex, rotation, vectors, queries,
                        probe, n_valid):
            return probed_search(placed, self.shard_of, self.local_slot,
                                 rotation, vectors, queries, probe, n_valid)

        return probed_step

    def search_probed(self, queries, probe, *, pad_to: int | None = None,
                      trim: bool = True
                      ) -> tuple[rerank_mod.RerankResult, SearchStats]:
        """Partial search over an EXPLICIT probe set (sharded fleet tier).

        probe (Q, P) int32 — per-query local cluster ids to search; -1
        entries are holes (probes owned by other engines) and contribute
        nothing. Returns the exact-reranked top-k over exactly those
        clusters; a row of all -1 probes yields ids -1 / dists inf. With
        pad_to=B the (cached) B-shaped executable is reused and results for
        real rows are identical to an unpadded call, like ``search``
        (``trim`` as there)."""
        queries = np.asarray(queries, np.float32)
        probe = np.asarray(probe, np.int32)
        nq = queries.shape[0]
        if probe.shape[0] != nq:
            raise ValueError(f"probe rows {probe.shape[0]} != queries {nq}")
        # local ids only — catching global-vs-local cid confusion here beats
        # XLA's silent gather clamp searching the wrong cluster downstream
        if probe.size and int(probe.max()) >= self.index.n_clusters:
            raise ValueError(
                f"probe id {int(probe.max())} out of range for this "
                f"engine's {self.index.n_clusters} local clusters — "
                f"search_probed takes LOCAL cluster ids (did you pass "
                f"global ids from cluster_filter on an unpartitioned "
                f"centroid set?)")
        p = probe.shape[1]
        b = nq if pad_to is None else int(pad_to)
        if b < nq:
            raise ValueError(f"pad_to={b} < batch size {nq}")
        queries = _pad_rows(queries, b, 0.0)
        probe = _pad_rows(probe, b, -1)
        key = ("probed", b, p)
        if key not in self._search_cache:
            self._search_cache[key] = self._build_probed_fn(b, p)
        fn = self._search_cache[key]
        out, stats = fn(self.placed, self.index.rotation, self.host.vectors,
                        queries, probe, jnp.int32(nq))
        return _trim(out, nq, trim), stats

    def search(self, queries, *, pad_to: int | None = None,
               trim: bool = True
               ) -> tuple[rerank_mod.RerankResult, SearchStats]:
        """Search; with pad_to=B >= len(queries) the batch is zero-padded to
        bucket B and the (cached) B-shaped executable is reused — results
        for the real queries are identical to an unpadded search.

        Padding happens on the host. ``trim=False`` returns all B rows (the
        rows past len(queries) are pads): a caller that reads the result on
        the host trims it there, where a device slice would compile once
        per distinct batch size while serving."""
        queries = np.asarray(queries, np.float32)
        nq = queries.shape[0]
        b = nq if pad_to is None else int(pad_to)
        if b < nq:
            raise ValueError(f"pad_to={b} < batch size {nq}")
        queries = _pad_rows(queries, b, 0.0)
        if b not in self._search_cache:
            self._search_cache[b] = self._build_search_fn(b)
        fn = self._search_cache[b]
        out, stats = fn(self.placed, self.index.centroids, self.index.rotation,
                        self.host.vectors, queries, jnp.int32(nq))
        return _trim(out, nq, trim), stats

    def search_bucketed(self, queries
                        ) -> tuple[rerank_mod.RerankResult, SearchStats]:
        """Route an arbitrary batch size through the engine's bucket ladder
        so any arrival size hits one of len(self.buckets) executables."""
        nq = len(queries)
        if not self.buckets:
            return self.search(queries)
        for b in self.buckets:
            if b >= nq:
                return self.search(queries, pad_to=b)
        raise ValueError(
            f"batch of {nq} exceeds largest bucket {self.buckets[-1]}; "
            f"split upstream (StreamingScheduler flushes at most max bucket)")

    # -- live mutation swap --------------------------------------------------
    def refresh(self, index: compact_index.CompactIndex,
                host: compact_index.HostStore | None = None
                ) -> "PIMCQGEngine":
        """Swap mutated/compacted arrays under the live engine.

        ``placed``/``host`` are read at dispatch time and flow into the
        compiled search functions as (functional) jit arguments, so the
        swap is atomic at flush granularity: in-flight flushes keep the
        old arrays, the next flush sees the new ones, and nothing
        retraces — provided shapes match (``MutableIndex`` pre-allocates
        slabs and vector capacity for exactly this reason). The fresh
        arrays are re-placed into the OLD arrays' device layout via
        ``distributed.elastic.reshard_like``."""
        if index.n_clusters != self.index.n_clusters \
                or index.budget != self.index.budget:
            raise ValueError(
                f"refresh needs matching shapes: "
                f"{index.n_clusters}x{index.budget} vs this engine's "
                f"{self.index.n_clusters}x{self.index.budget}")
        if host is not None:
            if host.vectors.shape != self.host.vectors.shape:
                raise ValueError(
                    f"host store grew {self.host.vectors.shape} -> "
                    f"{host.vectors.shape}; pre-allocate capacity "
                    f"(MutableIndex(capacity=...)) so swaps never retrace")
            self.host = host
        from ..distributed import elastic
        self.index = index
        self.placed = elastic.reshard_like(
            self.placed, _place(index, self.place, self.backend))
        return self

    @property
    def compile_count(self) -> int:
        """Number of distinct search executables built (one per shape)."""
        return len(self._search_cache)

    def warm(self, buckets: tuple[int, ...] | None = None) -> int:
        """Pre-compile the search executable for each bucket size (the
        engine's own ladder by default) so a timed stream measures serving,
        not tracing. Returns the number of executables built."""
        buckets = buckets if buckets is not None else self.buckets
        before = self.compile_count
        dummy = np.zeros((1, self.icfg.dim), np.float32)
        for b in buckets:
            res, _ = self.search(dummy, pad_to=int(b))
            np.asarray(res.ids)
        return self.compile_count - before

    # -- reporting ----------------------------------------------------------
    def footprint(self) -> dict:
        """Byte accounting with the live-vs-reclaimable split: ``n_valid``
        counts the occupied prefix (live + tombstoned under churn), served
        ``node_ids`` >= 0 counts live, and the pad rows above the occupied
        prefix are slab headroom spoken for by future inserts."""
        idx = self.index
        occupied = int(np.asarray(idx.n_valid).sum())
        live = int((np.asarray(idx.node_ids) >= 0).sum())
        reserved = idx.n_clusters * idx.budget - occupied
        return compact_index.footprint_report(
            self.icfg.dim, self.icfg.degree, live,
            tombstoned=occupied - live, slab=reserved)
