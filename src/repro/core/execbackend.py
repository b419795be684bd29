"""Pluggable execution backends for the serving topology (ISSUE 6).

*How a tier runs* is now a seam: ``EngineWorker``/``ShardWorker`` dispatch
through an ``ExecutionBackend`` instead of calling the engine directly.

  * ``InProcBackend`` — the default: delegates to ``engine.search`` /
    ``engine.search_probed`` on the current process's devices, exactly the
    pre-refactor behavior (bit-parity pinned by the unmodified
    test_topology/test_sharded/test_fleet suites).

  * ``MeshBackend`` — lays the shard groups out along a named axis of a
    real JAX device mesh (``launch.mesh.make_shard_mesh``) and runs the
    whole scatter -> ``search_probed`` -> gather path as ONE
    ``shard_map``-lowered step: every device searches its own partition's
    probed clusters and an ``all_gather`` collective returns each shard's
    partial top-k to the origin. Per-partition index arrays are stacked,
    padded to a common cluster count, and ``jax.device_put`` with
    shardings resolved through ``distributed.sharding`` (the dormant
    ``use_mesh``/``resolve_spec`` machinery, finally wired into serving).
    Validated on ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    CPU meshes; a multi-process ``jax.distributed`` launch builds the same
    mesh over per-host devices and runs the identical code path.

Bit-parity contract: the per-device block mirrors the in-process
``engine._build_probed_fn`` computation exactly (same lane capacity
formula, same cap table, same route/search/gather/rerank sequence), so
the mesh backend's partial top-k per shard — and hence the origin merge —
is bit-identical to the in-process backend and to a single engine
searching the same probed clusters (pinned in tests/test_execbackend.py
for shards {2, 4} on a forced 8-device host mesh).

Select a backend by registry key: ``topology(eng, shards=N, exec="mesh")``
or ``ServingTopology(..., exec="inproc"|"mesh"|instance)``.
"""

from __future__ import annotations

import types
from typing import Protocol

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["ExecutionBackend", "InProcBackend", "MeshBackend", "INPROC",
           "EXEC_BACKENDS", "resolve_exec_backend"]


class ExecutionBackend(Protocol):
    """Where/how a worker's flush actually executes. ``search`` and
    ``search_probed`` mirror the engine entry points (lazy results with
    async-dispatch semantics: ``.ids.is_ready()`` where supported) and may
    return the whole padded bucket: the worker keeps the leading rows;
    ``name`` is the registry key reported in TopologyReport."""

    name: str

    def search(self, engine, queries, *, pad_to): ...

    def search_probed(self, engine, queries, probe, *, pad_to): ...


class InProcBackend:
    """Default backend: run flushes on the engine in this process, on
    whatever device jax put the engine's arrays on (the historical
    behavior — bit-parity pinned by the unmodified serving test suites)."""

    name = "inproc"

    def search(self, engine, queries, *, pad_to):
        return engine.search(queries, pad_to=pad_to, trim=False)

    def search_probed(self, engine, queries, probe, *, pad_to):
        return engine.search_probed(queries, probe, pad_to=pad_to,
                                    trim=False)


INPROC = InProcBackend()


class MeshBackend:
    """Device-mesh execution of the sharded scatter/gather path.

    ``prepare(topology)`` stacks every shard group's placed index along a
    leading owner axis (cluster dimension padded to the widest partition —
    pad clusters are unreachable because probe tables only ever hold real
    local ids) and places the stack on ``mesh`` with ``P(axis)`` shardings
    resolved through ``distributed.sharding``. ``search_scattered`` then
    runs one jitted ``shard_map`` step per (bucket, nprobe) shape: each
    device executes its shard's ``_build_probed_fn``-equivalent block over
    ITS row of the scattered probe tables, and ``jax.lax.all_gather``
    brings every shard's partial top-k back to the origin — the gather
    collective the in-process backend only simulates with a host loop.

    Replication is the mesh's job here (one replica per shard laid on the
    axis); the in-process backend keeps the replica/hedging machinery.
    """

    name = "mesh"

    def __init__(self, mesh=None, axis: str = "shard"):
        self.mesh = mesh
        self.axis = axis
        self._cache: dict = {}
        self._ready = False

    # -- preparation ---------------------------------------------------------
    def prepare(self, topo) -> None:
        """Bind this backend to a sharded ServingTopology: build (or adopt)
        the mesh, stack + place the per-partition index arrays, and record
        the search configuration the step functions close over."""
        if self._ready:
            return
        leaders = [g[0] for g in topo.groups]
        n_owners = len(leaders)
        e0 = leaders[0]
        inner = {e.place.n_shards for e in leaders}
        if len(inner) != 1:
            raise ValueError(
                f"mesh backend needs every partition to share one "
                f"inner-shard count, got {sorted(inner)}")
        modes = {e.scfg.mode for e in leaders}
        if len(modes) != 1:
            raise ValueError(
                f"mesh backend lowers ONE ranking backend into the "
                f"shard_map step; heterogeneous modes {sorted(modes)} need "
                f"exec='inproc'")
        if self.mesh is None:
            from ..launch.mesh import make_shard_mesh
            self.mesh = make_shard_mesh(n_owners, self.axis)
        if self.mesh.shape[self.axis] != n_owners:
            raise ValueError(
                f"mesh axis {self.axis!r} has size "
                f"{self.mesh.shape[self.axis]} but the topology has "
                f"{n_owners} shard groups")

        self._scfg, self._dim = e0.scfg, e0.icfg.dim
        self._inner = e0.place.n_shards
        self._k = e0.scfg.k
        self._n_owners = n_owners
        self._stack_and_place(leaders)
        self._ready = True

    def _stack_and_place(self, leaders) -> None:
        """Stack the leaders' placed arrays along the owner axis and lay
        them on the mesh through ``distributed.elastic.place`` (the elastic
        substrate serving finally uses: the same resolve-spec + device_put
        path that grow/shrink ``replace_mesh`` events go through)."""
        def stack(leaves, cl_axis: int, fill):
            """Stack per-owner arrays along a new leading owner axis,
            padding ``cl_axis`` to the widest owner with ``fill`` (pad
            clusters are never probed: tables hold real local ids only)."""
            width = max(l.shape[cl_axis] for l in leaves)
            out = []
            for l in leaves:
                pad = [(0, 0)] * l.ndim
                pad[cl_axis] = (0, width - l.shape[cl_axis])
                out.append(np.pad(np.asarray(l), pad, constant_values=fill))
            return np.stack(out)

        placed = jax.tree.map(
            lambda *ls: jnp.asarray(stack(ls, 1, 0)),
            *[e.placed for e in leaders])
        shard_of = stack([e.place.shard_of for e in leaders], 0, 0)
        local_slot = stack([e.place.local_slot for e in leaders], 0, 0)

        from ..distributed import elastic
        from ..distributed import sharding as sharding_mod
        e0 = leaders[0]
        spec_sharded = P(self.axis)
        with sharding_mod.use_mesh(self.mesh):
            self._placed = elastic.place(
                placed, jax.tree.map(lambda _: spec_sharded, placed),
                self.mesh)
            self._shard_of = elastic.place(jnp.asarray(shard_of),
                                           spec_sharded, self.mesh)
            self._local_slot = elastic.place(jnp.asarray(local_slot),
                                             spec_sharded, self.mesh)
            # replicated operands: one rotation + one shared host store
            self._rotation = elastic.place(
                jnp.asarray(e0.index.rotation), P(), self.mesh)
            self._vectors = elastic.place(
                jnp.asarray(e0.host.vectors), P(), self.mesh)

    def refresh(self, topo) -> None:
        """Re-place the index stack after a live mutation swap
        (``ServingTopology.apply``): restack from the engines' refreshed
        arrays and re-place them on the SAME mesh. Shapes are stable (the
        ``MutableIndex`` contract), the arrays enter the compiled
        ``shard_map`` steps as jit arguments, and the mesh itself is
        unchanged — so every executable in ``_cache`` stays valid and the
        swap costs one transfer, zero retraces."""
        if not self._ready:
            raise RuntimeError("MeshBackend.refresh() before prepare()")
        self._stack_and_place([g[0] for g in topo.groups])

    # -- compiled step per (bucket, nprobe) shape ---------------------------
    def _build_fn(self, bucket: int, p: int):
        from . import engine as engine_mod
        from . import rerank as rerank_mod

        cfg, dim = self._scfg, self._dim
        s = self._inner
        axis = self.axis
        capacity = engine_mod._lane_capacity(bucket, p, s,
                                             cfg.lane_capacity_factor)
        cap_table = jnp.asarray(
            [engine_mod._lane_capacity(n, p, s, cfg.lane_capacity_factor)
             for n in range(bucket + 1)], jnp.int32)
        shard_fn = engine_mod._make_shard_search(cfg, dim)

        def block(placed, shard_of, local_slot, rotation, vectors,
                  queries, probe, n_valid):
            # per-device view: squeeze the owner axis (block size 1), then
            # run EXACTLY the in-process _build_probed_fn computation so
            # per-shard partial top-k is bit-identical to exec='inproc'
            pl = jax.tree.map(lambda a: a[0], placed)
            pr = probe[0]
            valid = jnp.arange(bucket, dtype=jnp.int32) < n_valid
            cap_valid = cap_table[jnp.clip(n_valid, 0, bucket)]
            lane_q, lane_cl, inv, _dropped = engine_mod.route_lanes(
                pr, shard_of[0], local_slot[0], valid, cap_valid,
                n_shards=s, capacity=capacity)
            gids, rank, hops = jax.vmap(
                shard_fn, in_axes=(0, None, None, 0, 0))(
                pl, rotation, queries, lane_q, lane_cl)
            flat_gids = gids.reshape(s * capacity, cfg.ef)
            safe = jnp.clip(inv, 0)
            cand = flat_gids[safe]
            cand = jnp.where((inv >= 0)[..., None], cand, -1)
            cand = cand.reshape(bucket, p * cfg.ef)
            out = rerank_mod.rerank(queries, cand, vectors, k=cfg.k)
            ids = jnp.where(valid[:, None], out.ids, -1)
            dists = jnp.where(valid[:, None], out.dists, jnp.inf)
            # the gather leg: every shard's partials to every device; the
            # origin (host) reads the replicated (O, B, k) result once
            return (jax.lax.all_gather(ids, axis),
                    jax.lax.all_gather(dists, axis))

        sh = P(axis)
        return jax.jit(jax.shard_map(
            block, mesh=self.mesh,
            in_specs=(jax.tree.map(lambda _: sh, self._placed),
                      sh, sh, P(), P(), P(), sh, P()),
            out_specs=(P(), P()),
            # all_gather makes the outputs replicated; the per-device
            # route/search block is not checked for that statically
            check_vma=False))

    # -- dispatch ------------------------------------------------------------
    def search_scattered(self, queries: np.ndarray, tables: np.ndarray,
                         *, pad_to: int):
        """One scattered flush: queries (B', D) with their per-owner probe
        tables (O, B', P) -> lazy (ids (O, B, k), dists (O, B, k)), B =
        pad_to. Row o is owner o's partial top-k (-1/inf where the owner
        was not touched), already gathered to the origin."""
        if not self._ready:
            raise RuntimeError("MeshBackend.prepare() was never called — "
                               "construct it through ServingTopology")
        nq, d = queries.shape
        b = int(pad_to)
        p = tables.shape[2]
        qb = np.zeros((b, d), np.float32)
        qb[:nq] = queries
        tb = np.full((self._n_owners, b, p), -1, np.int32)
        tb[:, :nq] = tables
        key = (b, p)
        if key not in self._cache:
            self._cache[key] = self._build_fn(b, p)
        from ..distributed import sharding as sharding_mod
        with sharding_mod.use_mesh(self.mesh):
            ids, dists = self._cache[key](
                self._placed, self._shard_of, self._local_slot,
                self._rotation, self._vectors, jnp.asarray(qb),
                jnp.asarray(tb), jnp.int32(nq))
        return types.SimpleNamespace(ids=ids, dists=dists)

    # EngineWorker reads engine.compile_count for its report; the mesh
    # worker's "engine" is this backend, whose executables live in _cache
    @property
    def compile_count(self) -> int:
        return len(self._cache)

    def warm(self, buckets, nprobe: int) -> int:
        """Pre-compile the shard_map step per bucket shape (all-hole probe
        tables: shape decides the executable, content does not)."""
        before = self.compile_count
        for b in buckets:
            q1 = np.zeros((1, self._dim), np.float32)
            t1 = np.full((self._n_owners, 1, nprobe), -1, np.int32)
            t1[0, 0, 0] = 0
            res = self.search_scattered(q1, t1[:, :1], pad_to=int(b))
            np.asarray(res.ids)
        return self.compile_count - before

    # Protocol completeness: a MeshBackend never serves replicated tiers,
    # but the seam's surface stays uniform so callers can probe it.
    def search(self, engine, queries, *, pad_to):
        raise NotImplementedError(
            "the mesh backend executes the sharded scatter path only; "
            "replicated tiers use exec='inproc'")

    def search_probed(self, engine, queries, probe, *, pad_to):
        raise NotImplementedError(
            "mesh execution dispatches whole scattered flushes via "
            "search_scattered, not per-engine search_probed")


# registry (mirrors core/backends.py idiom): name -> zero-arg factory, so
# every topology gets its OWN MeshBackend instance (prepare() binds state)
EXEC_BACKENDS = {
    "inproc": lambda: INPROC,
    "mesh": MeshBackend,
}


def resolve_exec_backend(spec) -> ExecutionBackend:
    """Registry key or instance -> backend instance (instances pass
    through, enabling a pre-built mesh: ``exec=MeshBackend(mesh=m)``)."""
    if isinstance(spec, str):
        try:
            return EXEC_BACKENDS[spec]()
        except KeyError:
            raise ValueError(
                f"unknown execution backend {spec!r}; registered: "
                f"{sorted(EXEC_BACKENDS)}") from None
    if hasattr(spec, "name") and (hasattr(spec, "search_probed")
                                  or hasattr(spec, "search_scattered")):
        return spec
    raise ValueError(f"exec must be a registry key or ExecutionBackend, "
                     f"got {spec!r}")
