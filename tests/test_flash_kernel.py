"""Pallas flash-attention forward kernel vs oracle (interpret mode)."""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attn import flash_attention_fwd


def _oracle(q, k, v, causal, q_offset=0):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(q.shape[-1])
    if causal:
        qp = q_offset + jnp.arange(q.shape[1])[:, None]
        kp = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(kp <= qp, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("bh,sq,sk,dk,dv,causal,dtype", [
    (2, 64, 64, 32, 32, True, jnp.float32),
    (3, 128, 128, 64, 64, True, jnp.float32),
    (1, 32, 96, 16, 24, False, jnp.float32),
    (2, 64, 64, 32, 32, True, jnp.bfloat16),
])
def test_flash_fwd_matches_oracle(bh, sq, sk, dk, dv, causal, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (bh, sq, dk), dtype)
    k = jax.random.normal(ks[1], (bh, sk, dk), dtype)
    v = jax.random.normal(ks[2], (bh, sk, dv), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, bq=32, bk=32,
                              interpret=True)
    want = _oracle(q, k, v, causal)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=atol)


def test_flash_fwd_q_offset_decode_chunk():
    """Chunked prefill: second half with q_offset equals full pass."""
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 64, 16))
    k = jax.random.normal(ks[1], (1, 64, 16))
    v = jax.random.normal(ks[2], (1, 64, 16))
    full = flash_attention_fwd(q, k, v, causal=True, bq=16, bk=16,
                               interpret=True)
    part = flash_attention_fwd(q[:, 32:], k, v, causal=True, bq=16, bk=16,
                               q_offset=32, interpret=True)
    np.testing.assert_allclose(np.asarray(part), np.asarray(full[:, 32:]),
                               atol=2e-5)
