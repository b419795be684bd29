"""Public jit'd wrappers over the Pallas kernels.

Dispatch policy (``prefer_kernel`` below — the single owner of the
Pallas-vs-ref choice; ``core.backends.MulFreeBackend.ranker`` and the
wrappers here both consult it):
  * On TPU the Pallas kernels run compiled (interpret=False).
  * On CPU (this container) the same kernels run in interpret mode when
    ``REPRO_FORCE_PALLAS=1`` (kernel tests / benchmarks); otherwise the
    pure-jnp reference path is used — it is the same math and lets XLA fuse
    the tiny per-beam-iteration evaluations (R ~ 32 rows), where a kernel
    launch would be pure overhead even on TPU.
  * ``full-scan`` sized problems (cluster_scan) prefer the kernel, as do
    wide rerank selections (``topk_select`` over C = nprobe*ef columns);
    the sharded tier's merge (``merge_topk`` over fanout*k columns) only
    crosses the threshold at deployment-sized fanouts.
  * The size threshold is ``_KERNEL_MIN_ROWS`` (256) unless overridden via
    the ``REPRO_KERNEL_MIN_ROWS`` env var (mirroring REPRO_FORCE_PALLAS;
    CI's forced-Pallas tier-1 leg lowers it so test-sized problems take the
    kernel path too). The decision is made at trace time: flipping either
    env var after an executable is cached does not retrace it.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import binary_ip as _k
from . import ref as _ref
from . import topk_select as _topk

__all__ = ["binary_ip_rank", "cluster_scan_topk", "topk_select",
           "merge_topk", "kernels_enabled", "prefer_kernel"]

_KERNEL_MIN_ROWS = 256  # below this, XLA-fused ref path wins even on TPU


def kernels_enabled() -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def _min_rows() -> int:
    """The active kernel-size threshold (REPRO_KERNEL_MIN_ROWS override)."""
    raw = os.environ.get("REPRO_KERNEL_MIN_ROWS")
    if raw is None:
        return _KERNEL_MIN_ROWS
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"REPRO_KERNEL_MIN_ROWS must be an integer, got {raw!r}") \
            from None
    if v < 0:
        raise ValueError(
            f"REPRO_KERNEL_MIN_ROWS must be >= 0, got {v}")
    return v


def prefer_kernel(n_rows: int) -> bool:
    """True when an n_rows-sized rank/scan/select should take the Pallas
    kernel (for the selection kernels, n_rows = candidate columns/query)."""
    return kernels_enabled() and n_rows >= _min_rows()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def binary_ip_rank(codes: jax.Array, f_add: jax.Array, lut: jax.Array,
                   sumq: jax.Array, s1: jax.Array, s2: jax.Array,
                   dim: int) -> jax.Array:
    """O3 mulfree rank of N nodes. See kernels/ref.py for exact semantics.

    Thin alias for ``MulFreeBackend.ranker`` (the backend owns its kernel;
    bound to the class, not the registry, so replacing the registered
    'mulfree' entry cannot change this wrapper's semantics)."""
    from ..core import backends  # deferred: kernels must not import core eagerly
    return backends.MulFreeBackend().ranker(
        codes, f_add, lut, sumq, s1, s2, dim)


def cluster_scan_topk(codes: jax.Array, f_add: jax.Array, lut: jax.Array,
                      sumq: jax.Array, s1: jax.Array, s2: jax.Array,
                      n_valid: jax.Array, *, dim: int, ef: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Fused GEMV-mode cluster scan + top-EF."""
    if prefer_kernel(codes.shape[0]):
        return _k.cluster_scan(codes, f_add, lut, sumq, s1, s2, n_valid,
                               dim=dim, ef=ef, interpret=_interpret())
    return _ref.cluster_scan_ref(codes, f_add, lut, sumq, s1, s2, dim, ef,
                                 n_valid)


def topk_select(cand_ids: jax.Array, dists: jax.Array, *, k: int
                ) -> tuple[jax.Array, jax.Array]:
    """Fused dedup + top-k over (Q, C) candidate rows (the origin rerank's
    selection stage). Kernel and ref are bitwise-identical; see
    kernels/ref.py topk_select_ref for the exact semantics."""
    if prefer_kernel(cand_ids.shape[-1]):
        return _topk.topk_select(cand_ids, dists, k=k,
                                 interpret=_interpret())
    return _ref.topk_select_ref(cand_ids, dists, k=k)


def merge_topk(part_ids: jax.Array, part_dists: jax.Array, *, k: int
               ) -> tuple[jax.Array, jax.Array]:
    """Merge O pre-sorted per-shard partial top-k runs (the sharded tier's
    origin gather/merge). Kernel and ref are bitwise-identical; see
    kernels/ref.py merge_topk_ref for the exact semantics."""
    if prefer_kernel(part_ids.shape[-1]):
        return _topk.merge_topk(part_ids, part_dists, k=k,
                                interpret=_interpret())
    return _ref.merge_topk_ref(part_ids, part_dists, k=k)
