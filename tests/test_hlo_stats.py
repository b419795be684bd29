"""Trip-count-weighted HLO accounting vs ground truth."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.launch.hlo_stats import weighted_totals, xla_cost_analysis


def _body(x, w):
    return jnp.tanh(x @ w), None


def test_scan_equals_unrolled_flops():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)

    def scanned(x, ws):
        y, _ = jax.lax.scan(_body, x, ws)
        return y

    def unrolled(x, ws):
        for i in range(8):
            x, _ = _body(x, ws[i])
        return x

    cs = jax.jit(scanned).lower(x, ws).compile()
    cu = jax.jit(unrolled).lower(x, ws).compile()
    ts, tu = weighted_totals(cs.as_text()), weighted_totals(cu.as_text())
    expect = 2.0 * 128 * 256 * 256 * 8
    assert ts.flops == expect
    assert tu.flops == expect
    # xla_cost_analysis normalizes the list-vs-dict return across JAX versions
    assert tu.flops == xla_cost_analysis(cu)["flops"]
    assert ts.n_while == 1


def test_nested_scan_multiplies():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws_inner = jnp.ones((5, 256, 256), jnp.float32)

    def outer(x, ws):
        def ob(xx, wo):
            y, _ = jax.lax.scan(_body, xx, ws_inner)
            return jnp.tanh(y @ wo), None
        y, _ = jax.lax.scan(ob, x, ws)
        return y

    c = jax.jit(outer).lower(
        x, jax.ShapeDtypeStruct((3, 256, 256), jnp.float32)).compile()
    t = weighted_totals(c.as_text())
    assert t.flops == 2.0 * 128 * 256 * 256 * (3 * 6)


def test_bytes_reasonable_for_simple_matmul():
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    c = jax.jit(lambda a, b: a @ b).lower(a, a).compile()
    t = weighted_totals(c.as_text())
    # 3 x 1MB tensors; allow up to 2x for copies/layout
    assert 3e6 <= t.bytes <= 7e6, t.bytes
    assert t.flops == 2.0 * 512 ** 3


def test_collective_accounting_psum():
    devs = jax.devices()
    if len(devs) < 1:
        return
    mesh = jax.make_mesh((1,), ("d",))
    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((256,), jnp.float32)).compile()
    t = weighted_totals(c.as_text())
    # single-device psum moves 0 bytes ((g-1)/g = 0)
    assert t.coll_bytes == 0.0
