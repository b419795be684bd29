"""Deterministic synthetic data pipelines.

Tokens: a mixture of Zipf-like unigram draws and short copy motifs so the
loss is neither trivial nor flat; fully determined by (seed, step) so any
host can regenerate its own shard — the standard recipe for restart-safe
distributed input pipelines (no data state in checkpoints beyond `step`).

Vectors: clustered Gaussians matched to the ANNS benchmark dimensionalities
(SIFT/SPACEV/SSN-like D), used by the PIMCQG benchmarks and tests.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TokenDataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    zipf_a: float = 1.2


def token_batch(cfg: TokenDataConfig, step: int | jax.Array) -> dict:
    """One global batch: {'tokens': (B, S) i32, 'labels': (B, S) i32}."""
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
    k1, k2 = jax.random.split(key)
    # Zipf via inverse-CDF on uniform: rank ~ u^(-1/(a-1)) (truncated)
    u = jax.random.uniform(k1, (cfg.global_batch, cfg.seq_len + 1),
                           minval=1e-6, maxval=1.0)
    rank = jnp.clip((u ** (-1.0 / (cfg.zipf_a - 1.0))).astype(jnp.int32) - 1,
                    0, cfg.vocab_size - 1)
    # sprinkle copy motifs: with p=.2 repeat the token 8 positions back
    rep = jax.random.uniform(k2, rank.shape) < 0.2
    shifted = jnp.roll(rank, 8, axis=1)
    seq = jnp.where(rep, shifted, rank)
    return {"tokens": seq[:, :-1], "labels": seq[:, 1:]}


def clustered_vectors(seed: int, n: int, d: int, n_clusters: int,
                      spread: float = 1.0, scale: float = 3.0
                      ) -> tuple[np.ndarray, np.ndarray]:
    """(x (N, D) f32, centers (K, D)) clustered-Gaussian dataset."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 1, (n_clusters, d)).astype(np.float32) * scale
    sizes = np.full(n_clusters, n // n_clusters)
    sizes[: n - sizes.sum()] += 1
    xs = [centers[i] + rng.normal(0, spread, (sizes[i], d)).astype(np.float32)
          for i in range(n_clusters)]
    x = np.concatenate(xs).astype(np.float32)
    rng.shuffle(x)
    return x, centers


def query_set(seed: int, x: np.ndarray, q: int, noise: float = 0.05
              ) -> np.ndarray:
    rng = np.random.default_rng(seed + 1)
    base = x[rng.choice(len(x), q)]
    return (base + rng.normal(0, noise, base.shape)).astype(np.float32)


def zipf_query_set(seed: int, x: np.ndarray, assignment: np.ndarray,
                   n_queries: int, *, s: float = 1.0,
                   hot_order: np.ndarray | None = None,
                   n_clusters: int | None = None, noise: float = 0.05
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Zipf-skewed query workload over an ANN corpus.

    Draws each query's TARGET CLUSTER from a Zipf(``s``) law over cluster
    popularity ranks, then perturbs a random member of that cluster —
    query probes concentrate on a controllable fraction of clusters, the
    way real user traffic does, while the per-query search problem stays
    identical to ``query_set``'s. ``assignment`` maps each corpus row to
    its cluster (the builder's cluster assignment, e.g.
    ``np.repeat/argmin`` over centroids). ``hot_order`` permutes WHICH
    clusters are hot: ``hot_order[r]`` is the cluster holding popularity
    rank r (default: cluster id == rank). Pass spatially-proximate
    clusters first to make hotspots geometric (whole probe neighborhoods
    hot), shuffled ids to scatter them.

    Returns (queries (Q, D) f32, target (Q,) int32 cluster of each draw)
    — the target vector doubles as the ground-truth heat histogram
    (``np.bincount(target)``)."""
    if s <= 0:
        raise ValueError(f"zipf exponent s must be > 0, got {s}")
    c = int(n_clusters) if n_clusters is not None \
        else int(np.asarray(assignment).max()) + 1
    if hot_order is None:
        hot_order = np.arange(c)
    hot_order = np.asarray(hot_order)
    if len(hot_order) != c or len(np.unique(hot_order)) != c:
        raise ValueError(f"hot_order must be a permutation of the {c} "
                         f"cluster ids")
    rng = np.random.default_rng(seed + 1)
    p = 1.0 / np.power(np.arange(1, c + 1, dtype=np.float64), s)
    p /= p.sum()
    target = hot_order[rng.choice(c, n_queries, p=p)].astype(np.int32)
    # pick a member row of each target cluster (clusters are never empty
    # in the builder's assignment; guard anyway by falling back to any row)
    members = [np.flatnonzero(assignment == cid) for cid in range(c)]
    rows = np.array([members[cid][rng.integers(len(members[cid]))]
                     if len(members[cid]) else rng.integers(len(x))
                     for cid in target])
    q = x[rows] + rng.normal(0, noise, (n_queries, x.shape[1]))
    return q.astype(np.float32), target


def drifting_hotspot_stream(seed: int, x: np.ndarray,
                            assignment: np.ndarray, n_queries: int,
                            n_rounds: int, *, s: float = 1.0,
                            hot_order: np.ndarray | None = None,
                            n_clusters: int | None = None,
                            shift_frac: float = 0.25,
                            noise: float = 0.05) -> list:
    """``n_rounds`` Zipf query sets whose hotspot DRIFTS between rounds:
    each round rotates ``hot_order`` by ``shift_frac`` of the cluster
    count, so yesterday's hot clusters cool and a fresh region heats up —
    the regime live heat-driven rebalancing exists for. Returns a list of
    (queries, target) tuples (one per round, each ``n_queries`` long)."""
    if not 1 <= n_rounds:
        raise ValueError(f"need n_rounds >= 1, got {n_rounds}")
    c = int(n_clusters) if n_clusters is not None \
        else int(np.asarray(assignment).max()) + 1
    order = np.arange(c) if hot_order is None else np.asarray(hot_order)
    shift = max(1, int(round(shift_frac * c)))
    rounds = []
    for r in range(n_rounds):
        rounds.append(zipf_query_set(
            seed + 1000 * r, x, assignment, n_queries, s=s,
            hot_order=np.roll(order, -shift * r), n_clusters=c,
            noise=noise))
    return rounds


def ground_truth(x: np.ndarray, queries: np.ndarray, k: int,
                 chunk: int = 512) -> np.ndarray:
    """Exact top-k ids by brute force (chunked over queries): a partial
    selection of the k nearest per row, then a sort of those k."""
    out = np.empty((len(queries), k), np.int64)
    x2 = (x * x).sum(-1)
    for s in range(0, len(queries), chunk):
        qc = queries[s:s + chunk]
        d2 = x2[None, :] - 2.0 * qc @ x.T
        top = np.argpartition(d2, k - 1, axis=1)[:, :k] if k < len(x) \
            else np.argsort(d2, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d2, top, axis=1), axis=1)
        out[s:s + chunk] = np.take_along_axis(top, order, axis=1)
    return out
