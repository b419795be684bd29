"""Logical->physical sharding resolution with divisibility fallbacks.

Param/activation specs in the model code are written *optimistically*
(e.g. attention heads over 'model'); at lowering time `resolve_spec` drops
any mesh axis that does not divide the corresponding array dimension —
exactly what a production framework does when an architecture's head count
(whisper: 20, internvl: 14) does not divide the TP degree: those weights are
replicated and the (dominant) FFN stays tensor-parallel.

The 'pod' axis: batch-sharding specs name ('pod', 'data'); on a single-pod
mesh 'pod' is absent and is silently dropped.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Sequence

import jax
from jax.sharding import (AbstractMesh, AxisType, Mesh, NamedSharding,
                          PartitionSpec as P)

_state = threading.local()


def current_mesh() -> Mesh | AbstractMesh | None:
    m = getattr(_state, "mesh", None)
    if m is not None:
        return m
    # fall back to the ambient mesh activated with ``jax.set_mesh`` (its
    # abstract form: the concrete one cannot be read under jit); inside a
    # shard_map body its axes are Manual and there is nothing to constrain
    ambient = jax.sharding.get_abstract_mesh()
    if ambient.empty or AxisType.Manual in ambient.axis_types:
        return None
    return ambient


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    prev = getattr(_state, "mesh", None)
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.mesh = prev


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= _axis_size(mesh, a)
        return n
    return mesh.shape[axis] if axis in mesh.shape else 0  # 0 = axis absent


def _clean_axis(mesh: Mesh, axis):
    """Drop absent axes from an entry; return None if nothing remains."""
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in mesh.shape)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return axis if axis in mesh.shape else None


def resolve_spec(mesh: Mesh, spec: P, shape: Sequence[int]) -> P:
    """Make `spec` legal for `shape` on `mesh`: drop absent axes, replicate
    dims the axis size does not divide."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axis in zip(shape, entries[: len(shape)]):
        axis = _clean_axis(mesh, axis)
        n = _axis_size(mesh, axis)
        out.append(axis if axis is not None and n > 0 and dim % max(n, 1) == 0
                   else None)
    return P(*out)


def resolve_tree(mesh: Mesh, params: Any, specs: Any) -> Any:
    """Pairwise resolve a spec tree against a param(-shape) tree."""
    def one(p, s):
        shape = p.shape if hasattr(p, "shape") else tuple(p)
        return resolve_spec(mesh, s, shape)
    return jax.tree.map(one, params, specs,
                        is_leaf=lambda x: isinstance(x, P))


def shardings_tree(mesh: Mesh, params: Any, specs: Any) -> Any:
    res = resolve_tree(mesh, params, specs)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), res,
                        is_leaf=lambda x: isinstance(x, P))


def constrain(x: jax.Array, *spec_entries) -> jax.Array:
    """Activation sharding hint with the same fallback semantics; no-op when
    no mesh is active (unit tests / CPU path)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve_spec(mesh, P(*spec_entries), x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
