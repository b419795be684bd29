"""The comparison that decides ``correct``.

Every answer due in the window is judged against the brute-force reference
(``corpus.exact_knn`` / ``corpus.exact_dists``, in the configuration's
dtype and metric) once the window has closed:

- ``lost``: queries that were neither answered nor shed (an answer that
  never came). Limit 0. A shed query is a refusal, counted in ``failed``.
- ``bad_rows``: answered rows with an id outside the corpus, a repeated id,
  a distance that is not finite, or distances out of ascending order.
  Limit 0.
- ``dist_gap``: the widest gap between a returned distance and the exact
  distance of the id it was returned with, over the scale of the float32
  terms that distance is computed from: for ``l2`` the squared distance
  over ``|q|^2 + |x|^2``, for ``ip`` the negated inner product over
  ``|q| |x|``. On a uint8 or int8 corpus the exact distance is an
  integer, computed without rounding. Limit from the configuration, set
  from readings of the program and of the control (``PERF.md``).
- ``recall``: recall@k of the answered rows against the exact top-k.
  Limit: the configuration's stated operating point.

The control is the reference put in the program's place one precision
step below the configuration's. For a float32 corpus (either metric):
its matmul in bfloat16 (``bf16_dot``), written out so that it computes the
same on every backend. bfloat16 holds every uint8 and int8 value exactly,
so on an integer corpus that matmul is exact and no control; there the
control is the exact answer with its distances rounded to bfloat16
(``bf16_round``), what a program whose rerank returned bfloat16 distances
would give.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["row_faults", "judge", "bf16_dot", "bf16_round"]


def bf16_dot(q, x):
    """(Qb, D) x (Xb, D) inner products in one bfloat16 pass with float32
    accumulation: what a float32 matmul at the TPU's default precision
    computes."""
    return jnp.dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)


def bf16_round(d: np.ndarray) -> np.ndarray:
    """float32 distances rounded to the nearest bfloat16 (ties to even)
    and back: the same on every backend."""
    return np.asarray(d, np.float32).astype(jnp.bfloat16).astype(np.float32)


def row_faults(ids: np.ndarray, dists: np.ndarray, n_corpus: int
               ) -> np.ndarray:
    """(R,) bool: the rows with an id outside [0, n_corpus), a repeated id,
    a non-finite distance, or distances out of ascending order."""
    out_of_range = ((ids < 0) | (ids >= n_corpus)).any(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    not_finite = ~np.isfinite(dists).all(axis=1)
    with np.errstate(invalid="ignore"):
        unordered = (np.diff(dists, axis=1) < 0).any(axis=1)
    return out_of_range | repeated | not_finite | unordered


def judge(*, ids, dists, answered, shed, ref_ids, exact_d, scale,
          n_corpus: int, limits: dict) -> dict:
    """The numbers compared, each with its limit.

    ids/dists (N, k): what the timed path returned for every due query;
    answered/shed (N,) bool; ref_ids (N, k): the exact top-k of each due
    query; exact_d/scale (N, k): the exact distance and scale of each
    returned id (rows that are not answered, or that hold an id out of
    range, are ignored there)."""
    lost = int((~answered & ~shed).sum())
    rows = np.flatnonzero(answered)
    faults = row_faults(ids[rows], dists[rows], n_corpus)
    good = rows[~faults]
    if len(good):
        gap = np.abs(dists[good].astype(np.float64) - exact_d[good]) \
            / np.maximum(scale[good], np.finfo(np.float32).tiny)
        dist_gap = float(gap.max())
    else:
        dist_gap = 0.0
    k = ids.shape[1]
    hits = [len(np.intersect1d(ids[i], ref_ids[i])) for i in rows]
    recall = float(np.sum(hits) / (len(rows) * k)) if len(rows) else 0.0
    checks = {
        "lost": {"value": lost, "limit": 0, "must": "<="},
        "bad_rows": {"value": int(faults.sum()), "limit": 0, "must": "<="},
        "dist_gap": {"value": dist_gap, "limit": float(limits["dist_gap"]),
                     "must": "<="},
        "recall": {"value": recall, "limit": float(limits["recall"]),
                   "must": ">="},
    }
    ok = all(c["value"] <= c["limit"] if c["must"] == "<="
             else c["value"] >= c["limit"] for c in checks.values())
    return {"correct": bool(ok and len(rows) > 0), "checks": checks,
            "recall": recall, "n_good": int(len(good))}
