"""Training launcher: config → mesh → jit train_step → checkpointed loop.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --preset smoke --steps 50 --ckpt-dir /tmp/ckpt [--resume]

Presets: smoke (per-arch reduced config, CPU-friendly), 100m (the ~100M
end-to-end example scale), full (the brief's exact config — production
mesh hardware required). Fault tolerance: manifest checkpoints every
--ckpt-every steps via the async writer; --resume restores the latest
valid step (a corrupt/torn directory is skipped, the previous one loads —
the node-failure path; see tests/test_checkpoint.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import manifest
from ..configs import get_config, get_smoke
from ..data.synthetic import TokenDataConfig, token_batch
from ..distributed import sharding as shard_lib
from ..launch.mesh import make_production_mesh, make_test_mesh
from ..models.model import build_model, make_train_step
from ..optim import adamw
from .compile_cache import enable_compile_cache


def preset_config(arch: str, preset: str):
    if preset == "full":
        return get_config(arch)
    cfg = get_smoke(arch)
    if preset == "100m":
        cfg = dataclasses.replace(
            cfg, n_layers=max(cfg.n_layers, 8),
            d_model=512, d_ff=2048 if cfg.d_ff else 0,
            n_heads=8 if cfg.n_heads else 0,
            n_kv_heads=min(8, max(cfg.n_kv_heads, 1)) if cfg.n_heads else 0,
            vocab_size=32000)
    return cfg


def run(arch: str, preset: str, steps: int, batch: int, seq: int,
        ckpt_dir: str | None, ckpt_every: int, resume: bool,
        mesh_kind: str, log_every: int = 10, seed: int = 0):
    cfg = preset_config(arch, preset)
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi")) \
        if mesh_kind in ("single", "multi") else make_test_mesh()

    key = jax.random.PRNGKey(seed)
    with mesh, shard_lib.use_mesh(mesh):
        params, specs = model.init(key)
        ocfg = adamw.AdamWConfig(warmup_steps=min(100, steps // 10 + 1),
                                 decay_steps=steps)
        opt_state = adamw.init(ocfg, params)
        step_fn = jax.jit(make_train_step(model, ocfg),
                          donate_argnums=(0, 1))

        start = 0
        writer = None
        if ckpt_dir:
            writer = manifest.AsyncWriter(ckpt_dir, config=cfg)
            if resume:
                import pathlib
                steps_avail = sorted(
                    (int(p.name.split("_")[1])
                     for p in pathlib.Path(ckpt_dir).glob("step_*")),
                    reverse=True) if pathlib.Path(ckpt_dir).exists() else []
                for latest in steps_avail:
                    try:
                        state = manifest.restore(
                            ckpt_dir, latest, {"p": params, "o": opt_state},
                            config=cfg)
                        params, opt_state = state["p"], state["o"]
                        start = latest
                        print(f"[train] resumed from step {latest}")
                        break
                    except Exception as e:              # noqa: BLE001
                        print(f"[train] step {latest} unusable ({e}); "
                              "falling back")

        dcfg = TokenDataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                               global_batch=batch, seed=seed)
        t0 = time.time()
        losses = []
        for step in range(start, steps):
            b = token_batch(dcfg, step)
            if cfg.n_patches:
                b["patches"] = jax.random.normal(
                    jax.random.fold_in(key, step),
                    (batch, cfg.n_patches, cfg.d_model), jnp.bfloat16)
            if cfg.n_frames:
                b["frames"] = jax.random.normal(
                    jax.random.fold_in(key, step),
                    (batch, cfg.n_frames, cfg.d_model), jnp.bfloat16)
            params, opt_state, m = step_fn(params, opt_state, b)
            losses.append(float(m["loss"]))
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t0
                tps = (step - start + 1) * batch * seq / max(dt, 1e-9)
                print(f"[train] step {step:5d} loss {losses[-1]:.4f} "
                      f"lr {float(m['lr']):.2e} gnorm "
                      f"{float(m['grad_norm']):.3f} tok/s {tps:,.0f}",
                      flush=True)
            if writer and ckpt_every and (step + 1) % ckpt_every == 0:
                writer.save(step + 1, {"p": params, "o": opt_state},
                            extra={"loss": losses[-1]})
        if writer:
            writer.save(steps, {"p": params, "o": opt_state},
                        extra={"loss": losses[-1]})
            writer.wait()
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="test",
                    choices=["test", "single", "multi"])
    args = ap.parse_args()
    enable_compile_cache()
    run(args.arch, args.preset, args.steps, args.batch, args.seq,
        args.ckpt_dir, args.ckpt_every, args.resume, args.mesh)


if __name__ == "__main__":
    main()
