"""PIMCQG engine under the production mesh — the paper's workload lowered
at billion scale (dry-run cells `pimcqg-engine × serve_b1/серve_b1_gemv`).

TPU mapping (DESIGN.md §2): the 'model' axis is the PU array — the
compact index (codes, f_add, adjacency, entries) is sharded on its
cluster-stack dim over 'model'; raw vectors for the host-rerank stage are
sharded over ('pod','data'); queries are data-parallel. Shapes follow the
paper's SIFT1B deployment: 1e9 nodes, 8192 IVF clusters (64 MB PU budget),
degree 32, D=128, nprobe 8, EF 40.

The lowering proves: zero cross-shard traffic during traversal (O1's
self-containment), candidate gather + rerank as the only collectives —
exactly the paper's host/PU split, expressed in XLA collectives.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import backends as backends_mod
from ..core import compact_index, engine, ivf, rerank as rerank_mod
from ..core.engine import _make_shard_search, route_lanes
from ..distributed import sharding as shard_lib

DP = ("pod", "data")


@dataclasses.dataclass(frozen=True)
class AnnsScale:
    """SIFT1B-shaped deployment (paper defaults)."""
    n: int = 10 ** 9
    dim: int = 128
    n_clusters: int = 8192
    budget: int = 131072          # padded nodes per cluster (~1e9/8192)
    degree: int = 32
    nprobe: int = 8
    ef: int = 40
    k: int = 10
    queries: int = 4096
    max_iters: int = 64

    @property
    def dim_padded(self):
        return self.dim + ((-self.dim) % 8)


def index_specs(s: AnnsScale, n_shards: int, mode: str = "mulfree"):
    """ShapeDtypeStruct stand-ins for the PIM-resident compact index,
    shard-major (S, C/S, ...) exactly like engine.PlacedIndex — built by
    the same ``engine.placed_specs`` helper, so the lowered tree always
    matches what ``_place`` produces (the backend contributes its own
    array slice; no per-field duplication here)."""
    cs = s.n_clusters // n_shards
    f = jax.ShapeDtypeStruct
    placed = engine.placed_specs(n_shards, cs, s.budget, s.degree, s.dim,
                                 backends_mod.get_backend(mode))
    host = dict(
        vectors=f((s.n, s.dim), jnp.float32),
        centroids=f((s.n_clusters, s.dim), jnp.float32),
        rotation=f((s.dim, s.dim), jnp.float32),
        queries=f((s.queries, s.dim), jnp.float32),
    )
    return placed, host


def placed_index_spec_tree(placed) -> engine.PlacedIndex:
    """PartitionSpecs: every PIM-resident array shards dim0 over 'model'."""
    return jax.tree.map(
        lambda l: P(*(("model",) + (None,) * (len(l.shape) - 1))), placed)


def sharded_rerank(queries, cand_ids, vectors, mesh, *, n_total: int,
                   k: int):
    """Owner-computes exact rerank (§Perf iteration P1).

    A naive `vectors[ids]` gather across the ('pod','data')-sharded raw
    store makes XLA replicate the whole multi-hundred-GB array (the
    baseline's 24.5 s collective term). Instead each data shard scores the
    candidates whose ids fall in its local range and a pmin over the data
    axes combines — the only cross-shard traffic is the (Q, C) id/distance
    tile (MBs).
    """
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    shard_rows = n_total // n_dp

    def body(q_rep, ids_rep, vec_local):
        idx = jax.lax.axis_index(dp_axes[-1])
        if len(dp_axes) > 1:
            idx = idx + mesh.shape[dp_axes[-1]] * jax.lax.axis_index(
                dp_axes[0])
        lo = idx * shard_rows
        local = ids_rep - lo
        mine = (local >= 0) & (local < shard_rows) & (ids_rep >= 0)
        safe = jnp.clip(local, 0, shard_rows - 1)
        cand = vec_local[safe]                          # (Q, C, D) local
        d2 = jnp.sum((q_rep[:, None, :] - cand) ** 2, axis=-1)
        d2 = jnp.where(mine, d2, jnp.inf)
        for ax in dp_axes:
            d2 = jax.lax.pmin(d2, ax)
        return d2

    spec_rep = P()
    d2 = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_rep, spec_rep, P(tuple(dp_axes), None)),
        out_specs=spec_rep, check_vma=False)(queries, cand_ids, vectors)
    # dedup ids (keep first occurrence) then top-k
    c = cand_ids.shape[-1]
    dup = jnp.any((cand_ids[:, None, :] == cand_ids[:, :, None])
                  & jnp.tril(jnp.ones((c, c), bool), k=-1)[None], axis=-1)
    d2 = jnp.where(dup | (cand_ids < 0), jnp.inf, d2)
    neg, pos = jax.lax.top_k(-d2, k)
    ids = jnp.take_along_axis(cand_ids, pos, axis=-1)
    ids = jnp.where(jnp.isfinite(-neg), ids, -1)
    return rerank_mod.RerankResult(ids.astype(jnp.int32),
                                   (-neg).astype(jnp.float32))


def build_search_step(s: AnnsScale, n_shards: int, scan: str = "beam",
                      mesh=None, owner_rerank: bool = False,
                      mode: str = "mulfree"):
    """search_step(placed, centroids, rotation, vectors, queries[, n_valid])
    — same function PIMCQGEngine jits, with round-robin placement maps.

    n_valid (optional traced scalar) makes the lowered executable
    shape-stable for serving: a partially-filled query batch padded up to
    s.queries masks its pad lanes out of routing/search/rerank, so one
    compiled program serves every arrival size up to the bucket."""
    scfg = engine.SearchConfig(nprobe=s.nprobe, ef=s.ef, k=s.k,
                               max_iters=s.max_iters, scan=scan, mode=mode)
    shard_of = jnp.asarray(np.arange(s.n_clusters, dtype=np.int32)
                           % n_shards)
    local_slot = jnp.asarray(np.arange(s.n_clusters, dtype=np.int32)
                             // n_shards)
    capacity = int(np.ceil(s.queries * s.nprobe / n_shards * 2.0))
    shard_fn = _make_shard_search(scfg, s.dim)

    def search_step(placed, centroids, rotation, vectors, queries,
                    n_valid=None):
        probe, _ = ivf.cluster_filter(queries, centroids, nprobe=s.nprobe)
        valid = None if n_valid is None else (
            jnp.arange(s.queries, dtype=jnp.int32) < n_valid)
        lane_q, lane_cl, inv, dropped = route_lanes(
            probe, shard_of, local_slot, valid, n_shards=n_shards,
            capacity=capacity)
        gids, rank, hops = jax.vmap(
            shard_fn, in_axes=(0, None, None, 0, 0))(
            placed, rotation, queries, lane_q, lane_cl)
        flat_gids = gids.reshape(n_shards * capacity, s.ef)
        safe = jnp.clip(inv, 0)
        cand = flat_gids[safe]
        cand = jnp.where((inv >= 0)[..., None], cand, -1)
        cand = cand.reshape(s.queries, s.nprobe * s.ef)
        if owner_rerank:
            out = sharded_rerank(queries, cand, vectors, mesh,
                                 n_total=s.n, k=s.k)
        else:
            out = rerank_mod.rerank(queries, cand, vectors, k=s.k)
        if valid is not None:
            out = rerank_mod.RerankResult(
                jnp.where(valid[:, None], out.ids, -1),
                jnp.where(valid[:, None], out.dists, jnp.inf))
        return out, hops, dropped

    return search_step


def model_flops(s: AnnsScale, hops_est: int = 32) -> float:
    """Useful-work yardstick: per lane, hops × R neighbor evaluations of a
    D-add LUT dot, plus the host rerank's exact distances."""
    lane_flops = hops_est * s.degree * 2.0 * s.dim_padded
    rerank_flops = s.nprobe * s.ef * 3.0 * s.dim
    return s.queries * (s.nprobe * lane_flops + rerank_flops)


def lower_anns(mesh, s: AnnsScale | None = None, scan: str = "beam",
               owner_rerank: bool = False, masked: bool = False,
               mode: str = "mulfree"):
    """Lower the billion-scale search step under `mesh`; returns lowered.

    masked=True lowers the shape-stable serving variant: the executable
    takes a replicated n_valid scalar so partially-filled (bucketed) query
    batches reuse this one compiled program. ``mode`` picks the ranking
    backend (any registered name lowers — the PIM-resident footprint is
    exactly the backend's array slice)."""
    s = s or AnnsScale()
    n_shards = mesh.shape["model"]
    placed, host = index_specs(s, n_shards, mode)
    pspec = placed_index_spec_tree(placed)
    with mesh, shard_lib.use_mesh(mesh):
        p_shard = jax.tree.map(
            lambda l, sp: NamedSharding(
                mesh, shard_lib.resolve_spec(mesh, sp, l.shape)),
            placed, pspec)
        h_shard = dict(
            vectors=NamedSharding(mesh, shard_lib.resolve_spec(
                mesh, P(DP, None), host["vectors"].shape)),
            centroids=NamedSharding(mesh, P()),
            rotation=NamedSharding(mesh, P()),
            queries=NamedSharding(mesh, shard_lib.resolve_spec(
                mesh, P(DP, None), host["queries"].shape)),
        )
        fn = build_search_step(s, n_shards, scan=scan, mesh=mesh,
                               owner_rerank=owner_rerank, mode=mode)
        in_sh = (p_shard, h_shard["centroids"], h_shard["rotation"],
                 h_shard["vectors"], h_shard["queries"])
        args = (placed, host["centroids"], host["rotation"],
                host["vectors"], host["queries"])
        if masked:
            in_sh += (NamedSharding(mesh, P()),)
            args += (jax.ShapeDtypeStruct((), jnp.int32),)
        jitted = jax.jit(fn, in_shardings=in_sh)
        lowered = jitted.lower(*args)
    return lowered, s


def main():
    import os
    assert "--xla_force_host_platform_device_count" in \
        os.environ.get("XLA_FLAGS", ""), \
        "run via: XLA_FLAGS=--xla_force_host_platform_device_count=512 " \
        "python -m repro.launch.anns_step"
    import argparse
    import json
    import pathlib
    import time

    from . import hlo_stats
    from .compile_cache import enable_compile_cache
    from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
    from .roofline import RooflineTerms

    from ..core import backends as backends_mod

    ap = argparse.ArgumentParser()
    ap.add_argument("--scan", default="beam", choices=["beam", "gemv"])
    ap.add_argument("--mode", default="mulfree",
                    choices=list(backends_mod.available_backends()),
                    help="ranking backend (registry key)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--owner-rerank", action="store_true")
    ap.add_argument("--masked", action="store_true",
                    help="lower the shape-stable (n_valid-masked) serving "
                         "variant used by the streaming scheduler")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    enable_compile_cache()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for mp in {"single": [False], "multi": [True],
               "both": [False, True]}[args.mesh]:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        mesh = make_production_mesh(multi_pod=mp)
        t0 = time.time()
        lowered, s = lower_anns(mesh, scan=args.scan,
                                owner_rerank=args.owner_rerank,
                                masked=args.masked, mode=args.mode)
        compiled = lowered.compile()
        totals = hlo_stats.weighted_totals(compiled.as_text())
        chips = mesh.size
        terms = RooflineTerms(
            flops=totals.flops * chips, hbm_bytes=totals.bytes * chips,
            coll_bytes=totals.coll_bytes * chips, chips=chips,
            peak_flops=PEAK_FLOPS_BF16, hbm_bw=HBM_BW, link_bw=ICI_BW,
            model_flops=model_flops(s))
        mem = {}
        try:
            ma = compiled.memory_analysis()
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes"):
                mem[attr] = int(getattr(ma, attr))
        except Exception as e:                              # noqa: BLE001
            mem["error"] = str(e)
        variant = f"serve_b1_{args.scan}" + \
            (f"_{args.mode}" if args.mode != "mulfree" else "") + \
            ("_ownrr" if args.owner_rerank else "") + \
            ("_masked" if args.masked else "")
        rec = dict(arch="pimcqg-engine", shape=variant,
                   mesh=mesh_name, status="ok", chips=chips,
                   memory=mem, roofline=terms.as_dict(),
                   hlo={"per_device_flops": totals.flops,
                        "per_device_bytes": totals.bytes,
                        "per_device_coll_bytes": totals.coll_bytes,
                        "coll_by_op": totals.coll_by_op},
                   wall_s=round(time.time() - t0, 2))
        path = out / f"pimcqg-engine__{variant}__{mesh_name}.json"
        path.write_text(json.dumps(rec, indent=1, default=float))
        r = rec["roofline"]
        print(f"[pimcqg-engine|{args.scan}|{mesh_name}] ok "
              f"({rec['wall_s']}s) bneck={r['bottleneck']} "
              f"tc={r['t_compute_s']:.3e} tm={r['t_memory_s']:.3e} "
              f"tx={r['t_collective_s']:.3e}", flush=True)


if __name__ == "__main__":
    main()
