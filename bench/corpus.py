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
query sets are), each from a uniformly chosen component; with
``query_shift`` s > 0 from means ``mu_c + s * g_c``, ``g_c ~ N(0, I)``, so
that they lie off the corpus's distribution (text queries on an image
base).

A configuration states its ``dtype`` (``float32``, ``uint8``, ``int8``) and
its ``metric`` (``l2``, ``ip``). An integer dtype also needs the generator's
``quantize: {"scale": s, "offset": o}``: a drawn value ``v`` is stored as
``clip(round(o + s * v))`` in the dtype's range, one component at a time,
so the device never holds the corpus in float32.

The reference is exact k-nearest-neighbour search: squared L2 distance or
the negated inner product, with the matmul at ``Precision.HIGHEST``, in
blocks of queries and corpus rows, ascending, ties broken towards the
lower id. On an integer corpus it is exact in float32 arithmetic (see
``exact_knn``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["RANGES", "METRICS", "Mixture", "seed_key", "make_corpus",
           "make_queries", "exact_knn", "exact_dists"]

# the dtypes a corpus is drawn in, each with its stored range (None: not
# quantized), and the metrics the reference ranks by
RANGES = {"float32": None, "uint8": (0, 255), "int8": (-128, 127)}
METRICS = ("l2", "ip")


def _check_metric(metric: str) -> str:
    """``metric``, refused where it is not one of ``METRICS``."""
    if metric not in METRICS:
        raise ValueError(f"metric {metric!r} is not one of {list(METRICS)}")
    return metric


def _check_exact(dtype: str, dim: int, metric: str) -> None:
    """Refuse an integer corpus on which the float32 reference would round.
    Every product, norm, partial sum and distance it forms is an integer
    of magnitude at most ``dim * w**2``, where ``w`` is the widest value
    (``max|v|``) for ``ip`` and the widest coordinate difference
    (``hi - lo``) for ``l2``; float32 holds each exactly up to 2**24:
    258-d for uint8 (either metric) and int8 ``l2``, 1,024-d for int8
    ``ip``."""
    r = RANGES[dtype]
    if r is None:
        return
    w = max(abs(r[0]), abs(r[1])) if metric == "ip" else r[1] - r[0]
    if dim * w ** 2 > 2 ** 24:
        raise ValueError(f"a {dim}-d {dtype} {metric} corpus is past the "
                         f"float32 reference's exact range "
                         f"(dim * {w}^2 <= 2^24)")


@dataclasses.dataclass(frozen=True)
class Mixture:
    """The generator's parameters, read from a configuration's file."""
    components: int
    rank: int
    center_scale: float
    noise: float
    dtype: str = "float32"
    quantize: tuple[float, float] | None = None   # (scale, offset)
    query_shift: float = 0.0

    @classmethod
    def from_config(cls, cfg: dict) -> "Mixture":
        """From a configuration's ``dtype``, ``dim``, ``metric`` and
        ``generator``; refused where the dtype is not one of ``RANGES`` or
        the metric one of ``METRICS``, where ``quantize`` is missing for an
        integer dtype or given for float32, or where the reference could
        not be exact (``_check_exact``)."""
        gen, dtype = cfg["generator"], cfg["dtype"]
        if dtype not in RANGES:
            raise ValueError(f"dtype {dtype!r} is not one of {list(RANGES)}")
        q = gen.get("quantize")
        if (q is None) != (RANGES[dtype] is None):
            raise ValueError(f"generator.quantize is required for an integer "
                             f"dtype and refused for float32: dtype "
                             f"{dtype!r}, quantize {q!r}")
        if q is not None and set(q) != {"scale", "offset"}:
            raise ValueError(f"generator.quantize takes scale and offset, "
                             f"got {sorted(q)}")
        shift = float(gen.get("query_shift", 0.0))
        if shift < 0:
            raise ValueError(f"generator.query_shift {shift} is negative")
        _check_exact(dtype, int(cfg["dim"]), _check_metric(cfg["metric"]))
        return cls(components=int(gen["components"]), rank=int(gen["rank"]),
                   center_scale=float(gen["center_scale"]),
                   noise=float(gen["noise"]), dtype=dtype,
                   quantize=None if q is None
                   else (float(q["scale"]), float(q["offset"])),
                   query_shift=shift)


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


def _quantize(v, mix: Mixture):
    """Drawn float32 values to the stored integer dtype:
    ``clip(round(offset + scale * v))``, rounding half to even."""
    lo, hi = RANGES[mix.dtype]
    scale, offset = mix.quantize
    return jnp.clip(jnp.round(offset + scale * v), lo, hi).astype(mix.dtype)


@functools.partial(jax.jit, static_argnames=("n", "dim", "mix"))
def make_corpus(key: jax.Array, *, n: int, dim: int, mix: Mixture
                ) -> jax.Array:
    """(n, dim) corpus in ``mix.dtype``, one component at a time on the
    device; an integer component is quantized as it is drawn, so no
    (n, dim) float32 array is ever formed."""
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
        v = mu[c] + z @ basis[c].T + mix.noise * e
        return v if mix.quantize is None else _quantize(v, mix)

    keys = jax.random.split(kpts, mix.components)
    x = jax.lax.map(one, (jnp.arange(mix.components), keys))
    return x.reshape(n, dim)


@functools.partial(jax.jit, static_argnames=("n", "dim", "mix"))
def make_queries(key: jax.Array, *, n: int, dim: int, mix: Mixture
                 ) -> jax.Array:
    """(n, dim) query pool in ``mix.dtype``, independent of the corpus's
    points."""
    kcomp = jax.random.split(jax.random.fold_in(key, 0))[0]
    mu, basis = _components(kcomp, dim, mix)
    if mix.query_shift:
        # its own key: fold_in(key, 2) seeds the program's index build
        mu = mu + mix.query_shift * jax.random.normal(
            jax.random.fold_in(key, 3), mu.shape)
    kc, kz, ke = jax.random.split(jax.random.fold_in(key, 1), 3)
    comp = jax.random.randint(kc, (n,), 0, mix.components)
    z = jax.random.normal(kz, (n, mix.rank))
    e = jax.random.normal(ke, (n, dim))
    x = mu[comp] + jnp.einsum("ndr,nr->nd", basis[comp], z) + mix.noise * e
    return x if mix.quantize is None else _quantize(x, mix)


def _divisor_block(n: int, cap: int) -> int:
    """The largest divisor of ``n`` not above ``cap``: corpus blocks that
    tile the corpus exactly, so no padded copy of it is ever made."""
    for b in range(min(cap, n), 0, -1):
        if n % b == 0:
            return b
    return 1


@functools.partial(jax.jit, static_argnames=("k", "block", "dot", "metric"))
def _knn_block(q, corpus, *, k: int, block: int, dot, metric: str):
    """Top-k of one query block over the whole corpus, scanned in corpus
    blocks of ``block`` rows, each cast to float32 as it is read."""
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    xb = corpus.reshape(-1, block, corpus.shape[1])
    integer = corpus.dtype != jnp.float32

    def step(carry, args):
        best_d, best_i = carry
        b, x = args
        x = x.astype(jnp.float32)
        if metric == "ip":
            score = -dot(q, x)
        elif integer:
            # |x|^2 - 2<q, x> = |q - x|^2 - |q|^2 is within the exact range
            # where |q|^2 + |x|^2 need not be
            score = q2 + (jnp.sum(x * x, axis=-1)[None, :] - 2.0 * dot(q, x))
        else:
            x2 = jnp.sum(x * x, axis=-1)
            score = q2 + x2[None, :] - 2.0 * dot(q, x)
        neg, idx = jax.lax.top_k(-score, k)
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
              metric: str = "l2", q_block: int = 1024, x_block: int = 32768,
              dot=highest_dot) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k ids and distances, (Q, k) each, on the host: squared L2
    distance for ``metric`` ``l2``, ``-<q, x>`` for ``ip``; ascending, ties
    towards the lower id. ``dot`` computes the (Qb, D) x (Xb, D) inner
    products; the reference uses float32 at ``Precision.HIGHEST``.

    On a uint8 or int8 corpus (and queries of its dtype) each corpus block
    is cast to float32 as it is read, and every product, norm, partial sum
    and distance is an integer of magnitude at most ``dim * w^2``: ``w`` is
    ``max|v|`` for ``ip`` and ``hi - lo`` for ``l2`` (an ``l2`` distance
    sums squared differences, up to 255 apart in either dtype). While that
    is at most 2**24 float32 holds each exactly, in any order of
    summation, and the distances and the ranking are exact: up to 258-d
    for uint8 and for int8 ``l2``, 1,024-d for int8 ``ip``. A corpus past
    that is refused."""
    metric = _check_metric(metric)
    _check_exact(str(corpus.dtype), corpus.shape[1], metric)
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
        i, d = _knn_block(jnp.asarray(q), corpus, k=k, block=block, dot=dot,
                          metric=metric)
        ids.append(np.asarray(i)[:rows])
        dists.append(np.asarray(d)[:rows])
    return np.concatenate(ids), np.concatenate(dists)


@jax.jit
def _pair_dists(q, x):
    d = q[:, None, :] - x
    return jnp.sum(d * d, axis=-1), (jnp.sum(q * q, axis=-1)[:, None]
                                     + jnp.sum(x * x, axis=-1))


@jax.jit
def _pair_ips(q, x):
    norms = jnp.sqrt(jnp.sum(q * q, axis=-1))[:, None] \
        * jnp.sqrt(jnp.sum(x * x, axis=-1))
    return -jnp.sum(q[:, None, :] * x, axis=-1), norms


def exact_dists(queries: np.ndarray, ids: np.ndarray, corpus: jax.Array,
                *, metric: str = "l2", block: int = 4096
                ) -> tuple[np.ndarray, np.ndarray]:
    """For each query row and each of its ids (all in range), the exact
    distance and the scale that a gap is measured against: for ``l2`` the
    squared distance, summed from the coordinate differences (no
    cancellation), over ``|q|^2 + |x|^2``; for ``ip`` ``-<q, x>``, summed
    from the coordinate products, over ``|q| |x|``. Integer rows are cast
    to float32 (exact there, as in ``exact_knn``)."""
    pair = {"l2": _pair_dists, "ip": _pair_ips}[_check_metric(metric)]
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
        d, sc = pair(jnp.asarray(q),
                     corpus[jnp.asarray(i)].astype(jnp.float32))
        out_d.append(np.asarray(d)[:n])
        out_s.append(np.asarray(sc)[:n])
    if not out_d:
        return np.zeros(ids.shape), np.ones(ids.shape)
    return np.concatenate(out_d), np.concatenate(out_s)
