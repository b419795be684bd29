"""shard_map DP trainer — explicit-collective data parallelism.

The jit/SPMD path (models/model.py make_train_step) lets XLA place
collectives; this trainer writes them by hand under shard_map so the
gradient reduction can be *compressed* (distributed/compress.py) and
hierarchical (reduce fully inside the pod, compress only the cross-pod
hop — the slow DCN link is the one that matters at 1000+ nodes).

Equivalence vs the jit path is asserted in tests/test_trainer.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.model import Model
from ..optim import adamw
from . import compress

__all__ = ["make_dp_train_step"]


def make_dp_train_step(model: Model, opt_cfg: adamw.AdamWConfig, mesh: Mesh,
                       *, compress_grads: bool = True,
                       error_feedback: bool = True) -> Callable:
    """Pure data parallelism over the ('pod','data') axes; params
    replicated per shard (model axis unused — compose with TP via the jit
    path when the model doesn't fit one chip).

    Returns train_step(params, opt_state, feedback, batch) ->
    (params, opt_state, feedback, metrics).
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def loss_fn(params, batch):
        loss, parts = model.loss(params, batch)
        return loss, parts

    def shard_body(params, opt_state, feedback, batch):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        if error_feedback:
            grads = compress.apply_feedback(grads, feedback)
        before = grads

        def reduce_one(g):
            g = g.astype(jnp.float32)
            for ax in data_axes[:-1]:            # fast axes: plain psum
                g = jax.lax.psum(g, ax) / jax.lax.psum(1, ax)
            slow = data_axes[-1]
            if compress_grads:
                return compress.compressed_psum_mean(g, slow)
            return jax.lax.psum(g, slow) / jax.lax.psum(1, slow)

        grads = jax.tree.map(reduce_one, grads)
        if error_feedback:
            feedback = jax.tree.map(
                lambda b, a: b.astype(jnp.float32) - a.astype(jnp.float32),
                before, grads)
        loss = jax.lax.pmean(loss, data_axes)
        params, opt_state, om = adamw.update(opt_cfg, grads, opt_state,
                                             params)
        return params, opt_state, feedback, {"loss": loss, **om}

    batch_spec = P(data_axes)
    rep = P()
    fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(rep, rep, rep, batch_spec),
        out_specs=(rep, rep, rep, rep),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1, 2))
