"""Serving launcher: batched prefill+decode, optional PIMCQG retrieval.

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --requests 16 --prompt-len 64 --gen 32 [--rag]

--rag wires the paper's engine into the decode loop through a pluggable
QUERY ENCODER (callable protocol, below): each decode step's logits are
turned into a (B, dim) query batch that streams into the PIMCQG
streaming scheduler (dynamic mini-batching over a shape-stable bucket
ladder + host rerank), demonstrating the retrieval substrate in its
production position. The default encoder mean-pools the logits over
positions and takes the probability-weighted token embedding (a real
model embedding, not a logit slice); pass any ``QueryEncoder`` callable
— or an ``ENCODERS`` registry name, resolved inside ``run`` where the
engine dim is known — to ``run(..., query_encoder=...)`` to swap it.
examples/rag_serve.py drives this path and demonstrates the swap.

--fleet N shards the retrieval stream across N engine replicas through
the FleetScheduler (core/fleet.py): round-robin / least-in-flight
routing, bounded admission queue, credit backpressure, and optional
deadline load shedding — the multi-engine serving tier in its
production position.

--fleet N --sharded PARTITIONS the index instead of replicating it:
the serving topology (core/topology.py) splits the clusters across N
engines (disjoint slices, ~1/N memory each) and scatters each
decode-step query to the <= nprobe engines owning its probed clusters,
gathering and merging partial top-k on the origin — the paper's Fig 18
multi-node serving shape under the RAG loop. Adding --replicas R
replicates EACH partition R ways (the hybrid tier: partition for
capacity, replicate for throughput), with tier-wide admission control.

--fleet N --sharded --exec mesh runs the same partitioned topology on a
REAL device mesh (one device per shard along a named axis): scatter ->
probed search -> gather lowers to one shard_map step with all_gather
collectives (core/execbackend.py). Needs N visible devices — force them
on CPU with XLA_FLAGS=--xla_force_host_platform_device_count=N, or
launch one process per host via jax.distributed for the identical code
path over real hosts. Results are bit-identical to --exec inproc.

--tenants "name:weight[:backend],..." splits the --rag retrieval stream
across named tenants (round-robin over the decode batch) served
weighted-fair by the topology's DWRR admission tier (core/topology.py,
ISSUE 8): each tenant's share of contended capacity tracks its weight,
and a ``backend`` entry pins that tenant's queries to shards declaring
the matching RankingBackend mode (requires --sharded; the shard
partitions are assigned the tenants' backends round-robin). Example:

    --rag --fleet 2 --sharded --tenants "latency:4:hamming,recall:1:exact"

Malformed entries, non-positive weights, unknown backends, and tenant
flags without the topology to serve them are argument ERRORS.

--churn F exercises the day-2 streaming-mutation path before retrieval:
the corpus is indexed through a ``MutableIndex`` (bounded append slabs +
tombstones), an F fraction is deleted and re-inserted, dirty clusters
are compacted offline, and the rebuilt state is swapped into the LIVE
scheduler (``ServingTopology.apply`` on the sharded tier,
``engine.refresh`` on the single-engine path) with zero recompiles.
With --fleet > 1 it requires --sharded: the replicated FleetScheduler
facade carries no mutation path.

--zipf S replaces the encoder-derived retrieval queries with a
Zipf(S)-skewed workload over the corpus clusters
(``data/synthetic.zipf_query_set``): query targets concentrate on a few
hot clusters the way production traffic does (S=1.0 is the classic
web-traffic law; larger S is hotter), which is the regime heat-aware
placement + hot-cluster replication (core/placement.py, ISSUE 10) exist
for. The query encoder is bypassed for the retrieval step — the flag
shapes WORKLOAD, not model state.

--sharded / --replicas without --fleet >= 2 is an argument ERROR, not a
silent single-engine run.
"""

from __future__ import annotations

import argparse
import time
from typing import Callable, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_smoke
from ..core import compact_index, engine
from ..core.backends import available_backends
from ..core.fleet import FleetScheduler, TenantSpec, TopologyConfig, \
    replicate_engine
from ..core.mutable_index import MutableIndex
from ..core.pipeline import StreamingScheduler, bucket_ladder
from ..core.topology import ServingTopology
from ..data.synthetic import clustered_vectors, zipf_query_set
from ..models.model import build_model
from .compile_cache import enable_compile_cache


class QueryEncoder(Protocol):
    """Maps decode-step logits to retrieval queries.

    __call__(logits (B, T, vocab) f32-like) -> (B, dim) np.float32 —
    one query embedding per in-flight request, in the engine's vector
    space dimension."""

    def __call__(self, logits: jax.Array) -> np.ndarray: ...


def mean_pool_encoder(params, dim: int) -> QueryEncoder:
    """Default encoder: probability-weighted mean token embedding.

    Mean-pools the logits over positions, softmaxes over the vocab, and
    takes the expected row of the model's own embedding table — a real
    (if simple) model embedding of the decode state, truncated to the
    engine's ``dim`` and L2-normalized. Requires ``params['embed']``
    ((vocab, d_model), true of every arch here)."""
    emb = params["embed"]
    if emb.shape[-1] < dim:
        raise ValueError(f"d_model {emb.shape[-1]} < engine dim {dim}")

    @jax.jit
    def _enc(logits):
        p = jax.nn.softmax(jnp.mean(logits.astype(jnp.float32), axis=1), -1)
        e = p @ emb.astype(jnp.float32)[:p.shape[-1]]     # (B, d_model)
        e = e[:, :dim]
        return e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True),
                               1e-6)

    def encode(logits: jax.Array) -> np.ndarray:
        return np.asarray(_enc(logits), np.float32)

    return encode


def logit_slice_encoder(dim: int) -> QueryEncoder:
    """The historical stub (first ``dim`` logits of position 0), kept as a
    named alternative encoder — and as proof the hook is pluggable."""
    def encode(logits: jax.Array) -> np.ndarray:
        return np.asarray(logits[:, 0, :dim], np.float32)
    return encode


# name -> factory(params, dim); resolved INSIDE run() where the engine dim
# is known, so CLIs pass names and never duplicate the dimension
ENCODERS: dict[str, Callable[..., QueryEncoder]] = {
    "mean-pool": mean_pool_encoder,
    "logit-slice": lambda params, dim: logit_slice_encoder(dim),
}


def parse_tenants(spec: str) -> list[TenantSpec]:
    """Parse --tenants "name:weight[:backend],..." into TenantSpecs.

    Every malformed entry raises ValueError with the offending text —
    tenant specs configure an SLO contract, so silent coercion is worse
    than an argument error."""
    out = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            raise ValueError(f"--tenants has an empty entry: {spec!r}")
        parts = [p.strip() for p in entry.split(":")]
        if len(parts) not in (2, 3) or not parts[0]:
            raise ValueError(
                f"bad tenant entry {entry!r}: expected name:weight[:backend]")
        name = parts[0]
        try:
            weight = float(parts[1])
        except ValueError:
            raise ValueError(f"tenant {name!r}: weight {parts[1]!r} is not "
                             f"a number") from None
        if not weight > 0:
            raise ValueError(
                f"tenant {name!r}: weight must be > 0, got {weight}")
        backend = parts[2] if len(parts) == 3 else None
        if backend is not None and backend not in available_backends():
            raise ValueError(
                f"tenant {name!r}: unknown backend {backend!r}; registered "
                f"backends: {available_backends()}")
        out.append(TenantSpec(name=name, weight=weight, backend=backend))
    names = [t.name for t in out]
    if len(set(names)) != len(names):
        raise ValueError(f"--tenants has duplicate tenant names: {names}")
    return out


def run(arch: str, requests: int, prompt_len: int, gen: int,
        rag: bool = False, seed: int = 0, verbose: bool = True,
        query_encoder: QueryEncoder | str | None = None, fleet: int = 1,
        sharded: bool = False, replicas: int = 1, exec: str = "inproc",
        tenants: str | list | None = None, churn: float = 0.0,
        zipf: float | None = None):
    # flag-consistency first: these used to be SILENTLY ignored, burning a
    # debugging session on a "sharded" run that never sharded anything
    if sharded and fleet < 2:
        raise ValueError(
            f"--sharded partitions the index across the fleet and needs "
            f"--fleet >= 2 (got --fleet {fleet}); a single engine has "
            f"nothing to partition")
    if replicas > 1 and not sharded:
        raise ValueError(
            f"--replicas {replicas} replicates each PARTITION and needs "
            f"--sharded; for plain replication use --fleet N alone")
    if replicas < 1:
        raise ValueError(f"--replicas must be >= 1, got {replicas}")
    if exec != "inproc" and not sharded:
        raise ValueError(
            f"--exec {exec} runs the SHARDED scatter/gather on a device "
            f"mesh and needs --sharded (with --fleet >= 2)")
    if exec == "mesh" and replicas > 1:
        raise ValueError(
            "--exec mesh drives one device per shard; replication on the "
            "mesh is a multi-process launch, not --replicas")
    if not 0.0 <= churn < 1.0:
        raise ValueError(f"--churn must be in [0, 1), got {churn}")
    if churn > 0 and not rag:
        raise ValueError("--churn mutates the retrieval corpus and "
                         "needs --rag")
    if zipf is not None:
        if not zipf > 0:
            raise ValueError(f"--zipf exponent must be > 0, got {zipf}")
        if not rag:
            raise ValueError("--zipf skews the retrieval stream and "
                             "needs --rag")
    if churn > 0 and fleet > 1 and not sharded:
        raise ValueError(
            "--churn needs the typed mutable topology (--sharded) or a "
            "single engine; the replicated FleetScheduler facade carries "
            "no day-2 mutation path")
    specs = None
    if tenants is not None:
        specs = parse_tenants(tenants) if isinstance(tenants, str) \
            else list(tenants)
        if not rag:
            raise ValueError("--tenants tags the retrieval stream and "
                             "needs --rag")
        if fleet < 2:
            raise ValueError(
                f"--tenants needs a serving topology to arbitrate "
                f"(--fleet >= 2; got --fleet {fleet})")
        tenant_backends = sorted({t.backend for t in specs
                                  if t.backend is not None})
        if tenant_backends and not sharded:
            raise ValueError(
                f"tenant backends {tenant_backends} pin tenants to shard "
                f"modes and need --sharded")
        if tenant_backends and fleet < len(tenant_backends):
            raise ValueError(
                f"{len(tenant_backends)} tenant backends "
                f"{tenant_backends} need --fleet >= {len(tenant_backends)} "
                f"shards to serve them (got --fleet {fleet})")
    cfg = get_smoke(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params, _ = model.init(key)

    eng = None
    mut = None
    if rag:
        x, _ = clustered_vectors(seed, 2000, 32, 8)
        icfg = compact_index.IndexConfig(dim=32, n_clusters=8, degree=8,
                                         knn_k=16)
        scfg = engine.SearchConfig(nprobe=2, ef=16, k=4)
        if churn > 0:
            # mutable corpus: pre-allocate enough append-slab headroom that
            # one churn round fits even if every insert routes to one
            # cluster (frozen-centroid assignment decides, not us)
            n_churn = max(1, int(round(churn * len(x))))
            mut = MutableIndex.build(key, x, icfg, slab=max(16, n_churn))
            eng = mut.to_engine(scfg, n_shards=2)
        else:
            eng = engine.PIMCQGEngine.build(key, x, icfg, scfg, n_shards=2)
        modes = None
        if specs is not None:
            tenant_backends = sorted({t.backend for t in specs
                                      if t.backend is not None})
            if tenant_backends:
                # heterogeneous fleet: spread the tenants' preferred
                # backends across the shard partitions round-robin
                modes = [tenant_backends[o % len(tenant_backends)]
                         for o in range(fleet)]
        if fleet > 1 and sharded:
            # partitioned tier (x replicas = the hybrid): each of `fleet`
            # shard groups owns a disjoint cluster slice served by
            # `replicas` engine replicas; queries scatter to the owners of
            # their probed clusters, partial top-k gathers on the origin,
            # and admission control applies tier-wide
            scheduler = TopologyConfig(
                shards=fleet, replicas=replicas, exec=exec,
                modes=modes, tenants=specs, mutable=churn > 0,
                buckets=bucket_ladder(max(requests, 1)),
                fill_threshold=max(requests // 2, 1),
                wait_limit_s=5e-3).build(eng)
        elif fleet > 1:
            # multi-engine tier: shard the decode-step query stream across
            # `fleet` replicas behind admission control (core/fleet.py)
            scheduler = FleetScheduler(
                replicate_engine(eng, fleet), tenants=specs,
                buckets=bucket_ladder(max(requests, 1)),
                fill_threshold=max(requests // 2, 1), wait_limit_s=5e-3)
        else:
            scheduler = StreamingScheduler(
                eng, buckets=bucket_ladder(max(requests, 1)),
                fill_threshold=max(requests // 2, 1), wait_limit_s=5e-3)
        if query_encoder is None:
            query_encoder = "mean-pool"
        if isinstance(query_encoder, str):
            query_encoder = ENCODERS[query_encoder](params, icfg.dim)
        if churn > 0:
            # one day-2 churn round before retrieval: delete + insert a
            # --churn fraction of the corpus, compact the dirty clusters,
            # and swap the rebuilt state into the live serving tier
            # (zero retraces: shapes are stable by construction)
            n_churn = max(1, int(round(churn * mut.n_live)))
            mut.delete(mut.live_ids()[:n_churn])
            rng = np.random.default_rng(seed + 1)
            mut.insert(np.arange(len(x), len(x) + n_churn),
                       rng.standard_normal((n_churn, icfg.dim))
                       .astype(np.float32))
            compacted = mut.compact()
            if isinstance(scheduler, ServingTopology):
                scheduler.apply(mut)
            else:
                eng.refresh(*mut.snapshot())
            if verbose:
                print(f"[serve] rag: churned {n_churn} deletes + "
                      f"{n_churn} inserts ({churn:.1%}), compacted "
                      f"{len(compacted)} clusters, swapped live")

    B = requests
    tokens = jax.random.randint(key, (B, prompt_len), 0, cfg.vocab_size)
    cache = model.init_cache(B, prompt_len + gen, dtype=jnp.float32)
    kw = {}
    if cfg.n_frames:
        kw["frames"] = jax.random.normal(key, (B, cfg.n_frames, cfg.d_model))
    if cfg.n_patches:
        kw["patches"] = jax.random.normal(key, (B, cfg.n_patches, cfg.d_model))

    prefill = jax.jit(lambda p, t, c: model.prefill(p, t, c, **kw))
    decode = jax.jit(model.decode)

    t0 = time.time()
    logits, cache = prefill(params, tokens, cache)
    out = [jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)]
    retrieved = rag_report = None
    for i in range(gen - 1):
        logits, cache = decode(params, out[-1], cache)
        out.append(jnp.argmax(logits[:, -1:], -1).astype(jnp.int32))
        if eng is not None and i == 0:
            if zipf is not None:
                # Zipf(S)-skewed workload over the corpus clusters: the
                # traffic shape heat-aware placement exists for (the
                # query encoder is bypassed — workload knob, not model)
                cents = np.asarray(eng.index.centroids)
                assign = np.argmin(
                    ((x[:, None, :] - cents[None, :, :]) ** 2).sum(-1),
                    axis=1).astype(np.int32)
                q, zipf_targets = zipf_query_set(seed, x, assign, B, s=zipf)
            else:
                # retrieval hook: the query encoder embeds the decode state
                q = query_encoder(logits)
            if specs is not None:
                # round-robin the decode batch across the tenants: every
                # tenant exercises its own admission queue/backend route
                labels = [specs[j % len(specs)].name for j in range(len(q))]
                rag_report = scheduler.run(q, tenant=labels)
            else:
                rag_report = scheduler.run(q)
            retrieved = rag_report.ids
    toks = jnp.concatenate(out, axis=1)
    jax.block_until_ready(toks)
    dt = time.time() - t0
    if verbose:
        print(f"[serve] {B} requests x ({prompt_len} prompt + {gen} gen) "
              f"in {dt:.2f}s -> {B * gen / dt:.1f} tok/s")
        if retrieved is not None:
            print(f"[serve] rag: retrieved neighbor ids (first 4 reqs): "
                  f"{retrieved[:4, :4].tolist()}")
            if zipf is not None:
                hist = np.bincount(zipf_targets)
                hot = np.argsort(-hist, kind="stable")[:3]
                print(f"[serve] rag: zipf(s={zipf:g}) workload — hottest "
                      f"clusters {hot.tolist()} hold "
                      f"{hist[hot].sum() / max(hist.sum(), 1):.0%} of "
                      f"{len(zipf_targets)} queries")
            if fleet > 1 and sharded:
                shares = [d["queries"] for d in rag_report.per_engine]
                sizes = [d["clusters"] for d in rag_report.per_engine]
                print(f"[serve] rag: sharded fleet={fleet}x{replicas} "
                      f"clusters/engine={sizes} "
                      f"fanout={rag_report.fanout_mean:.2f} "
                      f"scatter flushes={rag_report.n_flushes} "
                      f"merges={rag_report.n_merges} "
                      f"per-engine queries={shares} "
                      f"shed={rag_report.shed_fraction:.2f} "
                      f"p50={rag_report.p50_ms:.1f}ms")
            elif fleet > 1:
                shares = [d["queries"] for d in rag_report.per_engine]
                print(f"[serve] rag: fleet={fleet} ({rag_report.route}) "
                      f"buckets={scheduler.buckets} "
                      f"flushes={rag_report.n_flushes} "
                      f"per-engine queries={shares} "
                      f"shed={rag_report.shed_fraction:.2f} "
                      f"p50={rag_report.p50_ms:.1f}ms")
            else:
                print(f"[serve] rag: scheduler buckets={scheduler.buckets} "
                      f"flushes={rag_report.n_flushes} "
                      f"compiles={rag_report.compiles} "
                      f"p50={rag_report.p50_ms:.1f}ms")
            if specs is not None and getattr(rag_report, "tenants", None):
                for name, st in rag_report.tenants.items():
                    print(f"[serve] rag: tenant {name!r} w={st['weight']:g} "
                          f"backend={st['backend'] or 'any'} "
                          f"queries={st['n_queries']} shed={st['n_shed']} "
                          f"p50={st['p50_ms']:.1f}ms")
    return np.asarray(toks), retrieved


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--rag", action="store_true")
    ap.add_argument("--encoder", default="mean-pool", choices=list(ENCODERS),
                    help="query encoder for --rag (default: probability-"
                         "weighted mean token embedding)")
    ap.add_argument("--fleet", type=int, default=1,
                    help="shard --rag retrieval across N engine replicas "
                         "via the FleetScheduler (default 1: single-engine "
                         "StreamingScheduler)")
    ap.add_argument("--sharded", action="store_true",
                    help="with --fleet N: PARTITION the index across the N "
                         "engines (disjoint cluster slices, scatter/gather "
                         "routing) instead of replicating it")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --fleet N --sharded: replicate EACH "
                         "partition this many ways (the hybrid tier; "
                         "default 1)")
    ap.add_argument("--exec", default="inproc", choices=["inproc", "mesh"],
                    help="with --fleet N --sharded: execution backend — "
                         "'mesh' lays the shards along a device-mesh axis "
                         "and runs scatter/gather as collectives (needs N "
                         "devices: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N or a jax.distributed launch)")
    ap.add_argument("--tenants", default=None,
                    help="with --rag --fleet N: comma-separated "
                         "name:weight[:backend] tenant specs; the decode "
                         "batch is split round-robin across them and served "
                         "weighted-fair (DWRR) by the admission tier; a "
                         "backend entry pins the tenant to matching shards "
                         "(needs --sharded)")
    ap.add_argument("--zipf", type=float, default=None, metavar="S",
                    help="with --rag: draw the retrieval queries from a "
                         "Zipf(S) law over the corpus clusters instead of "
                         "the query encoder (S=1.0 = classic skew; the "
                         "workload heat-aware placement is built for)")
    ap.add_argument("--churn", type=float, default=0.0,
                    help="with --rag: delete+insert this fraction of the "
                         "retrieval corpus through the streaming mutation "
                         "tier (MutableIndex), compact, and swap the result "
                         "into the live scheduler before retrieval "
                         "(day-2 ops path; needs --sharded when --fleet>1)")
    args = ap.parse_args()
    # surface flag misuse as an argparse error (exit 2 + usage), not a
    # silently different topology
    if args.sharded and args.fleet < 2:
        ap.error(f"--sharded needs --fleet >= 2 (got --fleet {args.fleet})")
    if args.replicas > 1 and not args.sharded:
        ap.error("--replicas needs --sharded (plain replication is "
                 "--fleet N alone)")
    if args.replicas < 1:
        ap.error(f"--replicas must be >= 1, got {args.replicas}")
    if args.exec != "inproc" and not args.sharded:
        ap.error(f"--exec {args.exec} needs --sharded (with --fleet >= 2)")
    if args.exec == "mesh" and args.replicas > 1:
        ap.error("--exec mesh drives one device per shard; --replicas must "
                 "be 1 (replicate by launching more processes)")
    if args.tenants is not None:
        try:
            specs = parse_tenants(args.tenants)
        except ValueError as e:
            ap.error(str(e))
        if not args.rag:
            ap.error("--tenants tags the retrieval stream and needs --rag")
        if args.fleet < 2:
            ap.error(f"--tenants needs --fleet >= 2 "
                     f"(got --fleet {args.fleet})")
        if any(t.backend is not None for t in specs) and not args.sharded:
            ap.error("tenant backends pin tenants to shard modes and need "
                     "--sharded")
    if args.zipf is not None and not args.zipf > 0:
        ap.error(f"--zipf exponent must be > 0, got {args.zipf}")
    if args.zipf is not None and not args.rag:
        ap.error("--zipf skews the retrieval stream and needs --rag")
    if not 0.0 <= args.churn < 1.0:
        ap.error(f"--churn must be in [0, 1), got {args.churn}")
    if args.churn > 0 and not args.rag:
        ap.error("--churn mutates the retrieval corpus and needs --rag")
    if args.churn > 0 and args.fleet > 1 and not args.sharded:
        ap.error("--churn with --fleet > 1 needs --sharded (the typed "
                 "mutable topology; the replicated facade has no day-2 "
                 "mutation path)")
    enable_compile_cache()
    run(args.arch, args.requests, args.prompt_len, args.gen, args.rag,
        query_encoder=args.encoder, fleet=args.fleet, sharded=args.sharded,
        replicas=args.replicas, exec=args.exec, tenants=args.tenants,
        churn=args.churn, zipf=args.zipf)


if __name__ == "__main__":
    main()
