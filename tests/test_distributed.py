"""Sharding resolution, gradient compression, straggler policy, DP trainer."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import compress, sharding, straggler


def test_resolve_spec_divisibility():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # axis present but size 1 -> everything divides
    assert sharding.resolve_spec(mesh, P("model", None), (14, 8)) == \
        P("model", None)
    # absent axis dropped
    assert sharding.resolve_spec(mesh, P("pod", "model"), (4, 8)) == \
        P(None, "model")
    # tuple entries cleaned
    assert sharding.resolve_spec(mesh, P(("pod", "data"), None), (4, 8)) == \
        P("data", None)


def test_resolve_spec_indivisible_replicates():
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    # fake a mesh dict by monkeypatching axis size via a 1-dev mesh is not
    # possible; test the pure logic through _axis_size on a real mesh
    mesh = jax.make_mesh((1,), ("model",))
    # 14 % 1 == 0 -> sharding kept
    assert sharding.resolve_spec(mesh, P("model"), (14,)) == P("model")


def test_current_mesh_sees_ambient_mesh():
    """Regression (ISSUE 6 satellite): current_mesh() used to compute the
    ambient-mesh fallback into a local and then return None — dead code —
    so mesh-context callers outside use_mesh() always lost the mesh."""
    assert sharding.current_mesh() is None
    mesh = jax.make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):          # ambient activation, NOT use_mesh()
        got = sharding.current_mesh()
        assert got is not None
        assert dict(got.shape) == {"data": 1}
    assert sharding.current_mesh() is None


def test_current_mesh_use_mesh_takes_precedence():
    ours = jax.make_mesh((1,), ("model",))
    ambient = jax.make_mesh((1,), ("data",))
    with jax.set_mesh(ambient):
        with sharding.use_mesh(ours):
            assert sharding.current_mesh() is ours
        got = sharding.current_mesh()
        assert got is not None and got.axis_names == ("data",)


def test_quantize_roundtrip_error_small():
    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, (1024,)),
                    jnp.float32)
    q, s = compress.quantize_int8(x)
    x2 = compress.dequantize_int8(q, s)
    err = float(jnp.max(jnp.abs(x - x2)))
    assert err <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates():
    p = {"w": jnp.zeros((4,), jnp.float32)}
    fb = compress.init_feedback(p)
    g = {"w": jnp.asarray([1.0, -1.0, 0.5, 0.0])}
    g2 = compress.apply_feedback(g, fb)
    np.testing.assert_array_equal(np.asarray(g2["w"]), np.asarray(g["w"]))


def test_compressed_psum_single_device():
    """On a 1-device axis the compressed mean returns the input up to the
    int8 quantization step (|err| <= scale/2 = absmax/254)."""
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.arange(64, dtype=jnp.float32)
    f = jax.shard_map(lambda v: compress.compressed_psum_mean(v, "data"),
                      mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    out = f(x)
    tol = float(jnp.max(jnp.abs(x))) / 254.0 + 1e-6
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=tol)


def test_deadline_reissue():
    t = {"now": 0.0}
    dr = straggler.DeadlineReissue(k=2.0, clock=lambda: t["now"])
    dr.dispatch("a"); t["now"] = 1.0; assert dr.complete("a")
    # EWMA latency = 1.0 -> deadline 2.0; "b" dispatched at t=1.0
    dr.dispatch("b"); t["now"] = 3.5
    assert dr.poll() == ["b"]
    assert dr.poll() == []          # max_reissue=1
    dr.dispatch("b")                # speculative copy
    assert dr.complete("b")         # first completion wins
    assert not dr.complete("b")     # duplicate dropped
    assert dr.duplicate_results == 1


def test_dp_trainer_matches_jit_path():
    from repro.configs import get_smoke
    from repro.distributed.trainer import make_dp_train_step
    from repro.models.model import build_model, make_train_step
    from repro.optim import adamw

    cfg = get_smoke("phi3-mini-3.8b")
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params, _ = model.init(key)
    ocfg = adamw.AdamWConfig(warmup_steps=1, decay_steps=4, clip_norm=0.0)
    opt = adamw.init(ocfg, params)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (2, 16), 0, cfg.vocab_size)}

    jit_step = jax.jit(make_train_step(model, ocfg))
    p_ref, _, m_ref = jit_step(params, opt, batch)

    mesh = jax.make_mesh((1,), ("data",))
    dp_step = make_dp_train_step(model, ocfg, mesh, compress_grads=True)
    fb = compress.init_feedback(params)
    p_dp, _, fb2, m_dp = dp_step(params, opt, fb, batch)

    assert abs(float(m_ref["loss"]) - float(m_dp["loss"])) < 1e-3
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_dp)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-2)   # int8-compressed grads differ slightly
