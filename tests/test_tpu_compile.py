"""The main-path Pallas kernels compile for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles each kernel (``interpret=False``) for one device of a
described ``v5e:2x2`` and the HLO must hold the Mosaic custom call. What
the compiler refuses here (block shapes off the (8, 128) tiling, layouts
that disagree with XLA's, primitives Mosaic does not lower) would
otherwise first show on the chip. The topology is described inside a
fixture, never at import time: only one process at a time may load the
TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import binary_ip, topk_select


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                      # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("c", [320, 1024])
def test_topk_select_compiles(one_chip, c):
    """The origin rerank's selection at nprobe*ef = 8*40 and at 1024."""
    hlo = _hlo(lambda i, d: topk_select.topk_select(i, d, k=10),
               _spec(one_chip, (64, c), jnp.int32),
               _spec(one_chip, (64, c), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_merge_topk_compiles(one_chip):
    """The sharded origin's merge of 4 runs of 10."""
    hlo = _hlo(lambda i, d: topk_select.merge_topk(i, d, k=10),
               _spec(one_chip, (64, 40), jnp.int32),
               _spec(one_chip, (64, 40), jnp.float32))
    assert "tpu_custom_call" in hlo


def _rank_specs(one_chip, m=2048, w=16):
    return (_spec(one_chip, (m, w), jnp.uint8),
            _spec(one_chip, (m,), jnp.int32),
            _spec(one_chip, (w * 8,), jnp.int32),
            *(_spec(one_chip, (), jnp.int32) for _ in range(3)))


def test_binary_ip_rank_compiles(one_chip):
    hlo = _hlo(lambda *a: binary_ip.binary_ip_rank(*a, dim=128),
               *_rank_specs(one_chip))
    assert "tpu_custom_call" in hlo


def test_cluster_scan_compiles(one_chip):
    hlo = _hlo(lambda *a: binary_ip.cluster_scan(*a, dim=128, ef=40),
               *_rank_specs(one_chip), _spec(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in hlo
