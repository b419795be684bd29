import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the production mesh (16×16 single-pod /
2×16×16 multi-pod over 512 host placeholder devices), constructs the
step function (train_step / prefill_step / serve_step per the cell kind),
lowers it with ShapeDtypeStruct inputs under explicit in/out shardings,
compiles, and records:

  * memory analysis (bytes per device — proves the cell fits),
  * trip-count-weighted HLO FLOPs / bytes (launch/hlo_stats.py),
  * collective bytes by op,
  * the three roofline terms + dominant bottleneck (launch/roofline.py),
  * MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (serve) and the
    useful-compute ratio.

One JSON per cell lands in --out (default results/dryrun); EXPERIMENTS.md
§Dry-run/§Roofline are generated from these artifacts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both [--out results/dryrun] [--only-missing]
"""

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import all_arch_ids, get_config
from ..distributed import sharding as shard_lib
from ..launch import hlo_stats, roofline
from ..launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                           make_production_mesh)
from ..launch.shapes import CELLS, cell_applicable, input_specs
from ..models.model import (build_model, make_prefill_step, make_serve_step,
                            make_train_step)
from ..optim import adamw
from .compile_cache import enable_compile_cache

DP = ("pod", "data")


def _cache_specs(cache_shapes):
    """Cache sharding by leaf name+rank: batch over DP; KV caches shard the
    SEQUENCE dim over 'model' (flash-decoding: local scores + tiny softmax-
    stat reductions; sharding head_dim instead turns every score into a
    partial contraction XLA must all-reduce at (B,H,1,S) size — §Perf
    iteration C1); SSM heads / RG-LRU channels over 'model'."""
    def spec_for(path, leaf):
        name = ""
        for e in reversed(path):
            if hasattr(e, "name"):
                name = e.name
                break
        r = len(leaf.shape)
        if name in ("k", "v", "cross_k", "cross_v"):
            base = (DP, "model", None, None)
        elif name == "state":
            base = (DP, "model", None, None)
        elif name == "conv":
            base = (DP, None, "model")
        elif name == "h":
            base = (DP, "model")
        else:
            return P()
        pad = r - len(base)
        return P(*([None] * pad), *base)

    return jax.tree_util.tree_map_with_path(spec_for, cache_shapes)


def _shardings(mesh, shapes, specs):
    return jax.tree.map(
        lambda sh, sp: NamedSharding(
            mesh, shard_lib.resolve_spec(mesh, sp, sh.shape)),
        shapes, specs, is_leaf=lambda x: isinstance(x, P))


def _batch_specs(batch_shapes):
    def spec(leaf):
        return P(*((DP,) + (None,) * (len(leaf.shape) - 1)))
    return jax.tree.map(spec, batch_shapes)


FSDP_THRESHOLD_BYTES = 8e9    # params per model-shard above this -> FSDP


def _param_bytes_per_model_shard(shapes, mesh) -> float:
    tp = mesh.shape.get("model", 1)
    total = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    return total / tp


def _apply_fsdp(specs, shapes, mesh):
    """ZeRO-3/FSDP: additionally shard every ≥2-D weight over 'data'.
    XLA SPMD then inserts the per-layer gathers on use and reduce-scatters
    on the gradients — weight residency drops from P/tp to P/(tp·dp) per
    chip, the only way the ≥100B configs fit 16 GB.

    Placement preference:
      1. tensors that stay huge even model-sharded (MoE expert stacks):
         upgrade the 'model' dim to ('model','data') — the on-use gather
         then only spans the data axis of the tensor's own 1/tp slice;
      2. otherwise 'data' on a spare trailing weight dim;
      3. stacked-layer dim as a last resort."""
    dp = mesh.shape.get("data", 1)
    tp = mesh.shape.get("model", 1)

    def one(spec, shape):
        dims = shape.shape
        if len(dims) < 2:
            return spec
        entries = list(spec) + [None] * (len(dims) - len(spec))
        nbytes = shape.size * shape.dtype.itemsize
        if nbytes / (tp * dp) > 256e6:          # huge even fully sharded
            for i, e in enumerate(entries):
                if e == "model" and dims[i] % (tp * dp) == 0:
                    entries[i] = ("model", "data")
                    return P(*entries)
        order = list(range(1, len(dims))) + [0]
        for i in order:
            if entries[i] is None and dims[i] % dp == 0 and dims[i] >= dp:
                entries[i] = "data"
                return P(*entries)
        return spec

    return jax.tree.map(one, specs, shapes,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(arch: str, shape: str, multi_pod: bool):
    """Returns (lowered, aux) for the cell — lowering only, no compile."""
    cfg = get_config(arch)
    cell = CELLS[shape]
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    key = jax.random.PRNGKey(0)

    captured = {}

    def init_params_only(k):
        p, s = model.init(k)
        captured["specs"] = s
        return p

    with mesh, shard_lib.use_mesh(mesh):
        p_shapes = jax.eval_shape(init_params_only, key)
        p_specs = captured["specs"]
        fsdp = _param_bytes_per_model_shard(p_shapes, mesh) > \
            FSDP_THRESHOLD_BYTES
        if fsdp:
            p_specs = _apply_fsdp(p_specs, p_shapes, mesh)
        p_shard = _shardings(mesh, p_shapes, p_specs)
        inputs = input_specs(cfg, shape)
        in_shard = _shardings(mesh, inputs, _batch_specs(inputs))

        if cell.kind == "train":
            ocfg = adamw.AdamWConfig(
                moment_dtype="bfloat16" if cfg.param_count() > 2e11
                else "float32")
            o_shapes = jax.eval_shape(lambda p: adamw.init(ocfg, p), p_shapes)
            o_specs = adamw.AdamWState(P(), p_specs, p_specs)
            o_shard = _shardings(mesh, o_shapes, o_specs)
            fn = make_train_step(model, ocfg)
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, o_shard, in_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(p_shapes, o_shapes, inputs)
            tokens = cell.global_batch * cell.seq_len
        else:
            # serve cells: cache length = seq_len (decode) or exactly the
            # prefill length
            cache_len = cell.seq_len if cell.kind == "decode" else \
                cell.seq_len
            c_shapes = jax.eval_shape(
                lambda: model.init_cache(cell.global_batch, cache_len,
                                         dtype=jnp.bfloat16))
            c_specs = _cache_specs(c_shapes)
            c_shard = _shardings(mesh, c_shapes, c_specs)
            if cell.kind == "prefill":
                fn = make_prefill_step(model)
                extra_keys = [k for k in ("frames", "patches") if k in inputs]

                def prefill_pos(p, c, t, *extras):
                    return fn(p, c, t, **dict(zip(extra_keys, extras)))

                jitted = jax.jit(
                    prefill_pos,
                    in_shardings=(p_shard, c_shard, in_shard["tokens"],
                                  *[in_shard[k] for k in extra_keys]),
                    donate_argnums=(1,))
                lowered = jitted.lower(p_shapes, c_shapes, inputs["tokens"],
                                       *[inputs[k] for k in extra_keys])
            else:
                fn = make_serve_step(model)
                jitted = jax.jit(
                    fn,
                    in_shardings=(p_shard, c_shard, in_shard["tokens"]),
                    donate_argnums=(1,))
                lowered = jitted.lower(p_shapes, c_shapes, inputs["tokens"])
            tokens = cell.global_batch * (cell.seq_len
                                          if cell.kind == "prefill" else 1)

    n_active = cfg.active_param_count()
    model_flops = (6.0 if cell.kind == "train" else 2.0) * n_active * tokens
    return lowered, dict(mesh=mesh, model_flops=model_flops,
                         n_params=cfg.param_count(), n_active=n_active,
                         fsdp=fsdp)


def run_cell(arch: str, shape: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    ok, reason = cell_applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "status": "skip", "reason": reason}
    if not ok:
        return rec
    t0 = time.time()
    lowered, aux = build_cell(arch, shape, multi_pod)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:                                  # noqa: BLE001
        mem["error"] = str(e)

    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    totals = hlo_stats.weighted_totals(text)   # per-device quantities
    chips = aux["mesh"].size
    terms = roofline.RooflineTerms(
        flops=totals.flops * chips, hbm_bytes=totals.bytes * chips,
        coll_bytes=totals.coll_bytes * chips, chips=chips,
        peak_flops=PEAK_FLOPS_BF16, hbm_bw=HBM_BW, link_bw=ICI_BW,
        model_flops=aux["model_flops"])
    rec.update(
        status="ok",
        chips=chips,
        n_params=aux["n_params"],
        n_active=aux["n_active"],
        fsdp=aux["fsdp"],
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=mem,
        cost_analysis={k: ca[k] for k in ("flops", "bytes accessed")
                       if k in ca},
        hlo={"per_device_flops": totals.flops,
             "per_device_bytes": totals.bytes,
             "per_device_coll_bytes": totals.coll_bytes,
             "coll_by_op": totals.coll_by_op,
             "n_while": totals.n_while, "hlo_chars": len(text)},
        roofline=terms.as_dict(),
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--only-missing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    archs = list(all_arch_ids()) if args.arch == "all" else args.arch.split(",")
    shapes = list(CELLS) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = out / f"{arch}__{shape}__{mesh_name}.json"
                if args.only_missing and path.exists():
                    ok_prev = json.loads(path.read_text()).get("status") in \
                        ("ok", "skip")
                    if ok_prev:
                        continue
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape, mp)
                except Exception:                           # noqa: BLE001
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error",
                           "error": traceback.format_exc(limit=20)}
                rec["wall_s"] = round(time.time() - t0, 2)
                path.write_text(json.dumps(rec, indent=1, default=float))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" bottleneck={r['bottleneck']}"
                             f" tc={r['t_compute_s']:.3e}"
                             f" tm={r['t_memory_s']:.3e}"
                             f" tx={r['t_collective_s']:.3e}")
                elif status == "error":
                    extra = " " + rec["error"].splitlines()[-1][:120]
                print(f"[{arch:22s}|{shape:11s}|{mesh_name}] {status}"
                      f" ({rec['wall_s']}s){extra}", flush=True)


if __name__ == "__main__":
    main()
