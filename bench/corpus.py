"""Corpus, query pool and brute-force reference of one configuration.

Everything here is drawn from the run's seed on the device, in one jitted
call per array, and imports nothing of the program under test.

The corpus is a Gaussian mixture of low-rank components plus a small
isotropic noise, the shape of real descriptor sets (SIFT, GIST) whose
intrinsic dimension is far below their width. Component ``c`` has a mean
``mu_c`` (coordinates ~ N(0, center_scale**2)), a basis ``U_c`` of
``rank`` Gaussian columns of unit expected norm, and latent coordinates
``z ~ N(0, 1)``: a point is ``mu_c + U_c z + noise * e``. Every component
holds ``n / components`` points, in component-major order. Queries are
drawn independently from the same mixture (held out, as the sources'
query sets are), each from a uniformly chosen component.

The reference is exact k-nearest-neighbour search in float32: squared L2
distances with the matmul at ``Precision.HIGHEST``, in blocks of queries
and corpus rows, ties broken towards the lower id.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Mixture", "seed_key", "make_corpus", "make_queries",
           "exact_knn", "exact_dists"]


@dataclasses.dataclass(frozen=True)
class Mixture:
    """The generator's parameters, read from a configuration's file."""
    components: int
    rank: int
    center_scale: float
    noise: float

    @classmethod
    def from_config(cls, gen: dict) -> "Mixture":
        return cls(components=int(gen["components"]), rank=int(gen["rank"]),
                   center_scale=float(gen["center_scale"]),
                   noise=float(gen["noise"]))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed // 2**32)


def _components(key, dim: int, mix: Mixture):
    kmu, ku = jax.random.split(key)
    mu = mix.center_scale * jax.random.normal(kmu, (mix.components, dim))
    basis = jax.random.normal(ku, (mix.components, dim, mix.rank)) \
        / np.sqrt(dim)
    return mu, basis


@functools.partial(jax.jit, static_argnames=("n", "dim", "mix"))
def make_corpus(key: jax.Array, *, n: int, dim: int, mix: Mixture
                ) -> jax.Array:
    """(n, dim) float32 corpus, one component at a time on the device."""
    if n % mix.components:
        raise ValueError(f"n={n} is not a multiple of "
                         f"{mix.components} components")
    kcomp, kpts = jax.random.split(jax.random.fold_in(key, 0))
    mu, basis = _components(kcomp, dim, mix)
    per = n // mix.components

    def one(args):
        c, k = args
        kz, ke = jax.random.split(k)
        z = jax.random.normal(kz, (per, mix.rank))
        e = jax.random.normal(ke, (per, dim))
        return mu[c] + z @ basis[c].T + mix.noise * e

    keys = jax.random.split(kpts, mix.components)
    x = jax.lax.map(one, (jnp.arange(mix.components), keys))
    return x.reshape(n, dim).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n", "dim", "mix"))
def make_queries(key: jax.Array, *, n: int, dim: int, mix: Mixture
                 ) -> jax.Array:
    """(n, dim) float32 query pool, independent of the corpus's points."""
    kcomp = jax.random.split(jax.random.fold_in(key, 0))[0]
    mu, basis = _components(kcomp, dim, mix)
    kc, kz, ke = jax.random.split(jax.random.fold_in(key, 1), 3)
    comp = jax.random.randint(kc, (n,), 0, mix.components)
    z = jax.random.normal(kz, (n, mix.rank))
    e = jax.random.normal(ke, (n, dim))
    x = mu[comp] + jnp.einsum("ndr,nr->nd", basis[comp], z) + mix.noise * e
    return x.astype(jnp.float32)


def _divisor_block(n: int, cap: int) -> int:
    """The largest divisor of ``n`` not above ``cap``: corpus blocks that
    tile the corpus exactly, so no padded copy of it is ever made."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


@functools.partial(jax.jit, static_argnames=("k", "block", "dot"))
def _knn_block(q, corpus, *, k: int, block: int, dot):
    """Top-k of one query block over the whole corpus, scanned in corpus
    blocks of ``block`` rows."""
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    xb = corpus.reshape(-1, block, corpus.shape[1])

    def step(carry, args):
        best_d, best_i = carry
        b, x = args
        x2 = jnp.sum(x * x, axis=-1)
        neg, idx = jax.lax.top_k(-(q2 + x2[None, :] - 2.0 * dot(q, x)), k)
        d = jnp.concatenate([best_d, -neg], axis=1)
        i = jnp.concatenate([best_i, b * block + idx.astype(jnp.int32)],
                            axis=1)
        # ascending distance, then ascending id: sort the pair, keep k
        d, i = jax.lax.sort((d, i), num_keys=2)
        return (d[:, :k], i[:, :k]), None

    init = (jnp.full((q.shape[0], k), jnp.inf, jnp.float32),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    (d, i), _ = jax.lax.scan(step, init,
                             (jnp.arange(xb.shape[0], dtype=jnp.int32), xb))
    return i, d


def highest_dot(q, x):
    return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)


def exact_knn(queries: np.ndarray, corpus: jax.Array, k: int, *,
              q_block: int = 1024, x_block: int = 32768,
              dot=highest_dot) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k ids and squared distances, (Q, k) each, on the host.
    ``dot`` computes the (Qb, D) x (Xb, D) inner products; the reference
    uses float32 at ``Precision.HIGHEST``."""
    queries = np.asarray(queries, np.float32)
    nq = len(queries)
    qb = min(q_block, nq)
    block = _divisor_block(corpus.shape[0], x_block)
    ids, dists = [], []
    for s in range(0, nq, qb):
        q = queries[s:s + qb]
        rows = len(q)
        if rows < qb:
            q = np.concatenate([q, np.zeros((qb - rows, q.shape[1]),
                                            np.float32)])
        i, d = _knn_block(jnp.asarray(q), corpus, k=k, block=block, dot=dot)
        ids.append(np.asarray(i)[:rows])
        dists.append(np.asarray(d)[:rows])
    return np.concatenate(ids), np.concatenate(dists)


@jax.jit
def _pair_dists(q, x):
    d = q[:, None, :] - x
    return jnp.sum(d * d, axis=-1), (jnp.sum(q * q, axis=-1)[:, None]
                                     + jnp.sum(x * x, axis=-1))


def exact_dists(queries: np.ndarray, ids: np.ndarray, corpus: jax.Array,
                *, block: int = 4096) -> tuple[np.ndarray, np.ndarray]:
    """For each query row and each of its ids (all in range): the squared
    L2 distance, summed from the coordinate differences (no cancellation),
    and the scale ``|q|^2 + |x|^2`` that a gap is measured against."""
    queries = np.asarray(queries, np.float32)
    ids = np.asarray(ids, np.int32)
    rows = len(ids)
    b = min(block, max(rows, 1))
    out_d, out_s = [], []
    for s in range(0, rows, b):
        q, i = queries[s:s + b], ids[s:s + b]
        n = len(i)
        if n < b:
            q = np.concatenate([q, np.zeros((b - n, q.shape[1]), q.dtype)])
            i = np.concatenate([i, np.zeros((b - n, i.shape[1]), i.dtype)])
        d, sc = _pair_dists(jnp.asarray(q), corpus[jnp.asarray(i)])
        out_d.append(np.asarray(d)[:n])
        out_s.append(np.asarray(sc)[:n])
    if not out_d:
        return np.zeros(ids.shape), np.ones(ids.shape)
    return np.concatenate(out_d), np.concatenate(out_s)
