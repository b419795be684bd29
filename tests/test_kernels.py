"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import binary_ip as K
from repro.kernels import ref as R
from repro.kernels import ops


def _mk(rng, n, w, lut_mag=4096):
    codes = jnp.asarray(rng.integers(0, 256, (n, w), dtype=np.uint8))
    f_add = jnp.asarray(rng.integers(0, 1 << 20, (n,), dtype=np.int32))
    lut = jnp.asarray(rng.integers(-lut_mag, lut_mag, (w * 8,), dtype=np.int32))
    return codes, f_add, lut


@pytest.mark.parametrize("n,w,dim_off,s1,s2", [
    (8, 8, 0, 1, 31), (300, 8, 3, 2, 31), (1024, 16, 0, 2, 5),
    (77, 32, 7, 3, 31), (513, 16, 1, 4, 6), (2048, 64, 0, 2, 31),
])
def test_binary_ip_rank_matches_ref(rng, n, w, dim_off, s1, s2):
    codes, f_add, lut = _mk(rng, n, w)
    dim = w * 8 - dim_off
    lut = lut.at[dim:].set(0)
    sumq = jnp.int32(int(lut.sum()))
    out_k = K.binary_ip_rank(codes, f_add, lut, sumq, jnp.int32(s1),
                             jnp.int32(s2), dim=dim, interpret=True)
    out_r = R.binary_ip_rank_ref(codes, f_add, lut, sumq, jnp.int32(s1),
                                 jnp.int32(s2), dim)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("n,w,ef,nv", [
    (64, 8, 4, 64), (300, 16, 10, 250), (1024, 16, 32, 1000),
    (513, 8, 16, 513),
])
def test_cluster_scan_matches_ref(rng, n, w, ef, nv):
    codes, f_add, lut = _mk(rng, n, w)
    dim = w * 8
    sumq = jnp.int32(int(lut.sum()))
    ids_k, r_k = K.cluster_scan(codes, f_add, lut, sumq, jnp.int32(2),
                                jnp.int32(31), jnp.int32(nv), dim=dim, ef=ef,
                                interpret=True)
    ids_r, r_r = R.cluster_scan_ref(codes, f_add, lut, sumq, jnp.int32(2),
                                    jnp.int32(31), dim, ef, jnp.int32(nv))
    # both order by (rank, node id): bitwise equal, ties included
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))
    np.testing.assert_array_equal(np.asarray(ids_k), np.asarray(ids_r))


def test_ops_dispatch_paths(rng, monkeypatch):
    codes, f_add, lut = _mk(rng, 512, 8)
    dim = 64
    sumq = jnp.int32(int(lut.sum()))
    ref = R.binary_ip_rank_ref(codes, f_add, lut, sumq, jnp.int32(2),
                               jnp.int32(31), dim)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    out = ops.binary_ip_rank(codes, f_add, lut, sumq, jnp.int32(2),
                             jnp.int32(31), dim)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    monkeypatch.delenv("REPRO_FORCE_PALLAS")
    out2 = ops.binary_ip_rank(codes, f_add, lut, sumq, jnp.int32(2),
                              jnp.int32(31), dim)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
