"""Pluggable ranking backends for the query path (paper Figs 9/17/19).

The engine's defining degree of freedom is *which distance kernel ranks
candidates inside a PU*: the paper compares the mul-free O3 kernel against
the exact SymphonyQG estimator, and projects both onto GEMV-style PIM
substrates. Instead of threading ``mode`` strings and parallel positional
arrays (five of which used to be zero-filled dummies for the inactive
mode) through every layer, each variant is a ``RankingBackend``:

  * it OWNS its slice of per-node / per-cluster index arrays
    (``index_arrays`` — a registered pytree dataclass, placed shard-major
    next to the shared graph arrays inside ``engine.PlacedIndex``);
  * it OWNS its per-lane LUT preparation (``prepare_lanes`` — the host
    dispatch stage of Fig 4, vectorized over a shard's lane table);
  * it OWNS its candidate-ranking kernel (``rank_ids`` for beam expansion,
    ``rank_cluster`` for the full GEMV scan), choosing its Pallas vs
    reference implementation per the shared ``kernels.ops.prefer_kernel``
    policy;
  * it declares its rank dtype and pad/sentinel value so the traversal
    skeleton in core/beam_search.py is backend-agnostic.

Adding a backend = subclass + ``register_backend``; it then composes with
``beam``/``gemv`` scans, bucketed/padded serving, and the production-mesh
lowering in launch/anns_step.py with no further plumbing. ``HammingBackend``
(sign-only pre-rank over the canonical codes, no per-node metadata at all)
is the living proof of that claim.

``SearchConfig.mode`` strings ("mulfree" | "exact" | ...) are now just
registry keys — backward compatible with the old if-ladder spelling.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from . import mulfree, rabitq
from ..kernels import binary_ip as binary_ip_kernels
from ..kernels import ref as kernel_ref

__all__ = [
    "LaneConfig", "RankingBackend", "register_backend", "get_backend",
    "available_backends", "MulFreeBackend", "ExactBackend", "HammingBackend",
    "MulFreeArrays", "ExactArrays", "HammingArrays",
    "MulFreeLanes", "ExactLanes", "HammingLanes",
]

INT_MAX = jnp.iinfo(jnp.int32).max
F32_MAX = jnp.float32(jnp.finfo(jnp.float32).max)


def _register(cls):
    """Register a dataclass as a pytree (all fields are array leaves)."""
    fields = [f.name for f in dataclasses.fields(cls)]
    return jax.tree_util.register_dataclass(cls, data_fields=fields,
                                            meta_fields=[])


@dataclasses.dataclass(frozen=True)
class LaneConfig:
    """Static search geometry shared by every lane of one executable."""
    ef: int
    max_iters: int
    dim: int


# ---------------------------------------------------------------------------
# Per-backend pytrees: index-array slices and per-lane LUT bundles
# ---------------------------------------------------------------------------

@_register
@dataclasses.dataclass(frozen=True)
class MulFreeArrays:
    """O3's slice of the compact index (paper §IV-C)."""
    f_add: jax.Array    # (..., M) i32 — folded per-node additive factor
    rho: jax.Array      # (...,) f32  — cluster residual-norm constant
    shift1: jax.Array   # (...,) i32  — shift-add exponents for 1/alpha
    shift2: jax.Array   # (...,) i32


@_register
@dataclasses.dataclass(frozen=True)
class MulFreeLanes:
    """Integer LUT per lane; the scale is folded in host-side (Fig 4 step 1)."""
    lut: jax.Array      # (L, Dpad) i32
    sumq: jax.Array     # (L,) i32


@_register
@dataclasses.dataclass(frozen=True)
class ExactArrays:
    """SymphonyQG-baseline per-node factor tables (Fig 17's comparand)."""
    residual_norm: jax.Array  # (..., M) f32
    cos_theta: jax.Array      # (..., M) f32


@_register
@dataclasses.dataclass(frozen=True)
class ExactLanes:
    lut: jax.Array         # (L, Dpad) f32 — rotated unit query residual
    sum_lut: jax.Array     # (L,) f32
    query_norm: jax.Array  # (L,) f32


@_register
@dataclasses.dataclass(frozen=True)
class HammingArrays:
    """Sign-only pre-rank needs NOTHING beyond the shared canonical codes."""


@_register
@dataclasses.dataclass(frozen=True)
class HammingLanes:
    qcode: jax.Array    # (L, W) uint8 — packed sign code of the query residual


# ---------------------------------------------------------------------------
# The backend protocol
# ---------------------------------------------------------------------------

class RankingBackend:
    """One candidate-ranking variant of the in-PU search.

    Subclasses are stateless singletons (hashable by identity, so they can
    be jit static args). ``shard`` arguments below are the vmapped
    single-shard view of ``engine.PlacedIndex``: shared arrays have a
    (Cl, ...) cluster-stack leading shape and ``shard.arrays`` is this
    backend's own pytree with the same leading shape.
    """

    name: str = "?"
    rank_dtype: Any = jnp.int32

    @property
    def pad_rank(self):
        """Sentinel rank for -1 / invalid ids; sorts after every real rank."""
        raise NotImplementedError

    # -- index construction / placement / lowering --------------------------
    def index_arrays(self, idx) -> Any:
        """Slice this backend's per-node/per-cluster arrays (cluster-major)
        out of a built CompactIndex."""
        raise NotImplementedError

    def array_specs(self, lead: tuple[int, ...], budget: int, dim: int) -> Any:
        """ShapeDtypeStruct pytree matching ``index_arrays`` with leading
        dims ``lead`` (e.g. (S, C/S)) — for abstract lowering."""
        raise NotImplementedError

    # -- host dispatch stage -------------------------------------------------
    def prepare_lanes(self, qv, cv, rotation, arrays, lane_cl, dim: int):
        """Per-lane LUT prep for one shard. qv/cv (L, D) query/centroid rows
        (already gathered, clipped lanes), arrays = this backend's shard
        slice, lane_cl (L,) clipped local cluster ids."""
        raise NotImplementedError

    # -- PU-side ranking kernels ---------------------------------------------
    def rank_ids(self, shard, cl, ids, lane, dim: int):
        """Rank a gathered id set (beam expansion). ids (R,) with -1 pads ->
        pad_rank. Indexes the WHOLE shard stacks at (cl, ids) lazily:
        slicing the cluster out per lane would materialize (lanes, M, ...)
        under vmap (the §Perf P2 pathology)."""
        raise NotImplementedError

    def rank_cluster(self, shard, cl, lane, dim: int):
        """Rank every node of cluster ``cl`` (GEMV full scan, Fig 19).
        Returns (M,) ranks; invalid rows are masked by the caller."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, RankingBackend] = {}


def register_backend(backend: RankingBackend) -> RankingBackend:
    """Register (or replace) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> RankingBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown ranking backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# MulFree — the paper's O3 production kernel
# ---------------------------------------------------------------------------

class MulFreeBackend(RankingBackend):
    """O3: int LUT adds + shift-add 1/alpha (paper §IV-C, Fig 9)."""

    name = "mulfree"
    rank_dtype = jnp.int32

    @property
    def pad_rank(self):
        return INT_MAX

    def index_arrays(self, idx) -> MulFreeArrays:
        return MulFreeArrays(f_add=idx.f_add, rho=idx.rho,
                             shift1=idx.shift1, shift2=idx.shift2)

    def array_specs(self, lead, budget, dim) -> MulFreeArrays:
        f = jax.ShapeDtypeStruct
        return MulFreeArrays(
            f_add=f((*lead, budget), jnp.int32),
            rho=f(lead, jnp.float32),
            shift1=f(lead, jnp.int32), shift2=f(lead, jnp.int32))

    def prepare_lanes(self, qv, cv, rotation, arrays: MulFreeArrays,
                      lane_cl, dim) -> MulFreeLanes:
        def prep(qi, ci, rho):
            consts = mulfree.ClusterConstants(
                jnp.float32(0), rho, mulfree.AlphaShifts(
                    jnp.int32(0), jnp.int32(0), jnp.float32(0)))
            return mulfree.prepare_int_lut(qi, ci, rotation, consts, dim)
        lut, sumq = jax.vmap(prep)(qv, cv, arrays.rho[lane_cl])
        return MulFreeLanes(lut=lut, sumq=sumq)

    def ranker(self, codes, f_add, lut, sumq, s1, s2, dim):
        """The backend's O3 rank kernel. The Pallas-vs-ref policy is
        ``kernels.ops.prefer_kernel`` (its single owner); this method owns
        WHICH kernel/reference pair implements the backend's math."""
        from ..kernels import ops as kernel_ops  # deferred: env-dependent
        if kernel_ops.prefer_kernel(codes.shape[0]):
            return binary_ip_kernels.binary_ip_rank(
                codes, f_add, lut, sumq, s1, s2, dim=dim,
                interpret=jax.default_backend() != "tpu")
        return kernel_ref.binary_ip_rank_ref(codes, f_add, lut, sumq,
                                             s1, s2, dim)

    def rank_ids(self, shard, cl, ids, lane: MulFreeLanes, dim):
        a: MulFreeArrays = shard.arrays
        safe = jnp.clip(ids, 0)
        with jax.named_scope("expand"):               # the code gathers
            sub_codes = shard.codes[cl, safe]         # (R, W) uint8
            sub_f = a.f_add[cl, safe]                 # (R,) i32
        r = self.ranker(sub_codes, sub_f, lane.lut, lane.sumq,
                        a.shift1[cl], a.shift2[cl], dim)
        return jnp.where(ids >= 0, r, INT_MAX)

    def rank_cluster(self, shard, cl, lane: MulFreeLanes, dim):
        a: MulFreeArrays = shard.arrays
        return self.ranker(shard.codes[cl], a.f_add[cl], lane.lut, lane.sumq,
                           a.shift1[cl], a.shift2[cl], dim)


# ---------------------------------------------------------------------------
# Exact — SymphonyQG baseline (node-specific cos_theta)
# ---------------------------------------------------------------------------

class ExactBackend(RankingBackend):
    """Per-node fp estimator — the Fig 17 baseline PIMCQG is measured against."""

    name = "exact"
    rank_dtype = jnp.float32

    @property
    def pad_rank(self):
        return F32_MAX

    def index_arrays(self, idx) -> ExactArrays:
        return ExactArrays(residual_norm=idx.residual_norm,
                           cos_theta=idx.cos_theta)

    def array_specs(self, lead, budget, dim) -> ExactArrays:
        f = jax.ShapeDtypeStruct
        return ExactArrays(residual_norm=f((*lead, budget), jnp.float32),
                           cos_theta=f((*lead, budget), jnp.float32))

    def prepare_lanes(self, qv, cv, rotation, arrays, lane_cl,
                      dim) -> ExactLanes:
        qlut = jax.vmap(
            lambda qi, ci: rabitq.prepare_query(qi, ci, rotation))(qv, cv)
        pad = (-dim) % 8
        g = jnp.pad(qlut.lut, ((0, 0), (0, pad))) if pad else qlut.lut
        return ExactLanes(lut=g, sum_lut=qlut.sum_lut,
                          query_norm=qlut.query_norm)

    def _qlut(self, lane: ExactLanes) -> rabitq.QueryLUT:
        return rabitq.QueryLUT(lane.lut, lane.sum_lut, lane.query_norm)

    def rank_ids(self, shard, cl, ids, lane: ExactLanes, dim):
        a: ExactArrays = shard.arrays
        safe = jnp.clip(ids, 0)
        with jax.named_scope("expand"):               # the code gathers
            sub = rabitq.RabitQCodes(shard.codes[cl, safe],
                                     a.residual_norm[cl, safe],
                                     a.cos_theta[cl, safe], dim)
        d = rabitq.estimate_sqdist(sub, self._qlut(lane))
        return jnp.where(ids >= 0, d.astype(jnp.float32), F32_MAX)

    def rank_cluster(self, shard, cl, lane: ExactLanes, dim):
        a: ExactArrays = shard.arrays
        all_codes = rabitq.RabitQCodes(shard.codes[cl], a.residual_norm[cl],
                                       a.cos_theta[cl], dim)
        return rabitq.estimate_sqdist(
            all_codes, self._qlut(lane)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Hamming — sign-only pre-rank (extensibility proof; zero per-node metadata)
# ---------------------------------------------------------------------------

class HammingBackend(RankingBackend):
    """Popcount(code XOR sign(q)) — the cheapest conceivable PU kernel.

    Ranks by angle only (ignores residual norms entirely), so recall
    trails O3; the host's exact rerank recovers much of it at equal EF.
    Exists to prove a backend with NO per-node metadata and a non-LUT
    lane payload (one packed sign code, D/8 bytes/lane) slots into every
    layer — beam, gemv, bucketed serving, mesh lowering — untouched.
    """

    name = "hamming"
    rank_dtype = jnp.int32

    @property
    def pad_rank(self):
        return INT_MAX

    def index_arrays(self, idx) -> HammingArrays:
        return HammingArrays()

    def array_specs(self, lead, budget, dim) -> HammingArrays:
        return HammingArrays()

    def prepare_lanes(self, qv, cv, rotation, arrays, lane_cl,
                      dim) -> HammingLanes:
        return HammingLanes(qcode=jax.vmap(
            lambda qi, ci: rabitq.sign_code(qi, ci, rotation, dim=dim))(
                qv, cv))

    def _hamming(self, codes, qcode, dim):
        # padded dims are 0 in both node codes and the query code -> inert;
        # popcounts cast up BEFORE the sum (W bytes can exceed uint8 range)
        pc = jnp.bitwise_count(jnp.bitwise_xor(codes, qcode))
        return jnp.sum(pc.astype(jnp.int32), axis=-1)

    def rank_ids(self, shard, cl, ids, lane: HammingLanes, dim):
        safe = jnp.clip(ids, 0)
        with jax.named_scope("expand"):               # the code gathers
            sub_codes = shard.codes[cl, safe]
        r = self._hamming(sub_codes, lane.qcode, dim)
        return jnp.where(ids >= 0, r, INT_MAX)

    def rank_cluster(self, shard, cl, lane: HammingLanes, dim):
        return self._hamming(shard.codes[cl], lane.qcode, dim)


register_backend(MulFreeBackend())
register_backend(ExactBackend())
register_backend(HammingBackend())
