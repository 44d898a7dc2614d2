"""The serving driver: partition, layer-wise-infer, then serve traffic.

The full serving stack on one command line: partition a graph (edge OR
vertex partitioner — the embedding store shards by masters resp. owners),
run the distributed layer-wise inference engine to materialise the
per-layer embedding stores (gnn/inference.py), then drive a Poisson request
trace through the micro-batched online path (repro.serve) and report
per-worker p50/p99 latency and sustainable QPS on the paper's cluster.

  PYTHONPATH=src python -m repro.launch.gnn_serve --graph OR --scale 0.05 \
      --partitioner hep100 --k 4 --model sage --qps 100 --smoke
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.core import study
from repro.core.edge_partition import EDGE_PARTITIONERS, partition_edges
from repro.core.graph import paper_graph
from repro.core.metrics import edge_partition_metrics, vertex_partition_metrics
from repro.core.partition_book import build_vertex_book
from repro.core.vertex_partition import VERTEX_PARTITIONERS, partition_vertices
from repro.core.wire import CODECS
from repro.gnn.feature_store import CACHE_POLICIES
from repro.gnn.inference import (
    LayerwiseInference,
    edge_assignment_from_vertex,
)
from repro.gnn.models import GNNSpec, init_params
from repro.launch.compile_cache import use_compile_cache
from repro.serve import build_serving, run_serving_sim


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="OR", choices=["HO", "DI", "EN", "EU", "OR"])
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--partitioner", default="hep100",
                    help="edge partitioner (store shards by masters) or "
                         "vertex partitioner (store shards by owners)")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--model", default="sage", choices=["sage", "gcn", "gat"])
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--agg-backend", default="scatter",
                    choices=["scatter", "tiled", "pallas"])
    ap.add_argument("--qps", type=float, default=100.0,
                    help="offered load (Poisson arrivals, whole cluster)")
    ap.add_argument("--requests", type=int, default=1000,
                    help="length of the simulated request trace")
    ap.add_argument("--hops", type=int, default=1,
                    help="final layers recomputed per request (1..layers-1); "
                         "the rest is read from the embedding store")
    ap.add_argument("--fanout", type=int, default=10)
    ap.add_argument("--batch", type=int, default=32,
                    help="micro-batch size cap")
    ap.add_argument("--max-wait", type=float, default=5e-4,
                    help="seconds a request may wait for its micro-batch")
    ap.add_argument("--codec", default="fp32", choices=list(CODECS),
                    help="wire codec (core/wire.py) on the embedding store: "
                         "remote-miss rows are shipped encoded and decoded "
                         "at the reader; service time is priced from "
                         "encoded bytes")
    ap.add_argument("--cache-policy", default="none",
                    choices=list(CACHE_POLICIES))
    ap.add_argument("--cache-budget", type=int, default=0,
                    help="cached remote embedding rows per worker")
    ap.add_argument("--out-json", default="",
                    help="write the study-format serving row here")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="record the span/counter timeline to PATH (Chrome "
                         "trace-event JSON, schema gnn-trace/v1: inference "
                         "layers + real gather/compute spans on the host "
                         "process, the request lifecycle on the simulated "
                         "clock) and write the reconciliation report to "
                         "PATH.report.json")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="SPEC",
                    help="deterministic fault injection (repeatable): "
                         "worker-death@t:0.5,worker:1 kills a serving "
                         "worker at virtual time t; its requests fail over "
                         "to surviving workers (replica-aware "
                         "master_assignment re-derivation) and EVERY "
                         "request is still answered")
    ap.add_argument("--detect-delay", type=float, default=0.0,
                    help="seconds before a death is detected (rerouted "
                         "requests become visible to survivors after it)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-fast: trim the request trace")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 200)

    plan = None
    if args.inject_fault:
        from repro.fault import FaultPlan, FaultSpecError
        try:
            plan = FaultPlan.parse(args.inject_fault, seed=args.seed)
        except FaultSpecError as e:
            print(f"[serve] bad --inject-fault: {e}")
            sys.exit(1)
        print(f"[serve] fault plan: "
              f"{'; '.join(ev.describe() for ev in plan.events)}")

    tracer = None
    if args.trace:
        from repro.obs import Tracer, install
        tracer = install(Tracer())

    g = paper_graph(args.graph, scale=args.scale, seed=0)
    print(f"[serve] graph {args.graph}: {g.num_vertices} vertices, "
          f"{g.num_edges} edges")
    spec = GNNSpec(model=args.model, feature_dim=args.features,
                   hidden_dim=args.hidden, num_classes=args.classes,
                   num_layers=args.layers, agg_backend=args.agg_backend)
    rng = np.random.default_rng(args.seed)
    feats = rng.normal(size=(g.num_vertices, args.features)).astype(np.float32)
    params = init_params(spec, seed=args.seed)

    # ---------------------------------------------------------- partition
    t0 = time.perf_counter()
    if args.partitioner in EDGE_PARTITIONERS:
        edge_assignment = partition_edges(g, args.k, args.partitioner,
                                          seed=args.seed)
        pt = time.perf_counter() - t0
        m = edge_partition_metrics(g, edge_assignment, args.k)
        quality = m.replication_factor
        print(f"[serve] edge-partitioned in {pt:.2f}s: "
              f"rf={m.replication_factor:.2f} edge_bal={m.edge_balance:.2f}")
        owner = None  # derived from masters below
    else:
        assert args.partitioner in VERTEX_PARTITIONERS, (
            f"unknown partitioner {args.partitioner!r}; edge options "
            f"{sorted(EDGE_PARTITIONERS)}, vertex options "
            f"{sorted(VERTEX_PARTITIONERS)}")
        owner = partition_vertices(g, args.k, args.partitioner, seed=args.seed)
        pt = time.perf_counter() - t0
        m = vertex_partition_metrics(g, owner, args.k)
        quality = m.edge_cut
        print(f"[serve] vertex-partitioned in {pt:.2f}s: "
              f"edge_cut={m.edge_cut:.3f} vertex_bal={m.vertex_balance:.2f}")
        edge_assignment = edge_assignment_from_vertex(g, owner)

    # ------------------------------------------- layer-wise embedding pass
    engine = LayerwiseInference.build(
        g, edge_assignment, args.k, spec, params, feats)
    embeddings = engine.run()
    if owner is None:
        owner = engine.book.master_assignment()
    vbook = build_vertex_book(g, owner, args.k)
    dims = "/".join(str(e.shape[1]) for e in embeddings)
    print(f"[serve] layer-wise inference: {len(embeddings)} layers "
          f"(dims {dims}) in {sum(engine.layer_times):.2f}s host, "
          f"halo traffic {engine.sync_bytes()/2**20:.1f} MiB/pass")

    # ------------------------------------------------------- online serving
    engines, batchers, store = build_serving(
        g, vbook, spec, params, embeddings,
        hops=args.hops, fanout=args.fanout, max_batch=args.batch,
        max_wait=args.max_wait, cache_policy=args.cache_policy,
        cache_budget=args.cache_budget, seed=args.seed, codec=args.codec,
    )
    if args.cache_budget:
        print(f"[serve] embedding cache: policy={args.cache_policy} "
              f"budget={args.cache_budget}/worker "
              f"(filled {store.cache_sizes.tolist()})")
    request_ids = rng.integers(0, g.num_vertices, args.requests)
    arrivals = np.sort(rng.uniform(0.0, args.requests / args.qps,
                                   args.requests))
    failover = None
    if plan is not None and plan.events_of("worker-death"):
        from repro.fault import recovery as fault_recovery
        ev = plan.events_of("worker-death")[0]
        dead = plan.resolve_worker(ev, args.k)
        # replica-aware only for edge partitions: mirrors already hold the
        # dead master's vertices; vertex partitions spread deterministically
        book = engine.book if args.partitioner in EDGE_PARTITIONERS else None
        failover = fault_recovery.failover_assignment(
            owner, dead, args.k, book=book)
        moved = int((np.asarray(owner) == dead).sum())
        print(f"[serve] failover map: worker {dead} dies, {moved} vertices "
              f"re-mastered ({'replica-aware' if book is not None else 'spread'})")
    report = run_serving_sim(engines, batchers, owner, request_ids, arrivals,
                             fault_plan=plan, failover_owner=failover,
                             detect_delay=args.detect_delay)

    for row in report.worker_rows():
        print(f"[serve] worker {row['worker']}: served {row['served']:5d}  "
              f"p50 {row['p50']*1e3:7.2f} ms  p99 {row['p99']*1e3:7.2f} ms  "
              f"sustainable {row['qps_sustainable']:8.0f} qps")
    print(f"[serve] cluster: offered {args.qps:.0f} qps, served "
          f"{report.served()} requests in {report.duration:.2f}s  "
          f"p50 {report.p50()*1e3:.2f} ms  p99 {report.p99()*1e3:.2f} ms  "
          f"sustainable {report.sustainable_qps():.0f} qps/cluster")
    print(f"[serve] store traffic: hit_rate {report.fetch.hit_rate:.2f}  "
          f"miss {report.fetch.miss_bytes/2**20:.2f} MiB  "
          f"wire {report.fetch.wire_bytes/2**20:.2f} MiB ({args.codec})  "
          f"host compute p50 {np.percentile(report.host_time, 50)*1e3:.2f} "
          f"ms/batch")
    if report.fault_time is not None:
        ts = report.transition_stats()
        answered = report.served() == args.requests
        print(f"[serve] worker-death: worker {report.dead_worker} died at "
              f"t={ts['fault_time']:.3f}s, {ts['rerouted']} requests "
              f"rerouted, transition window {ts['window']*1e3:.1f} ms "
              f"({ts['requests']} requests, p50 {ts['p50']*1e3:.2f} ms, "
              f"p99 {ts['p99']*1e3:.2f} ms)")
        print(f"[serve] every request answered: {answered} "
              f"({report.served()}/{args.requests})")
        if not answered:
            sys.exit(1)

    if args.out_json:
        row = study.serve_result_row(
            args.graph, args.partitioner, args.k, spec, report,
            qps=args.qps, hops=args.hops, fanout=args.fanout,
            max_batch=args.batch, max_wait=args.max_wait,
            cache_policy=args.cache_policy, cache_budget=args.cache_budget,
            partition_time=pt, partition_quality=quality, codec=args.codec,
        )
        study.write_rows([row], args.out_json)
        print(f"[serve] wrote study row -> {args.out_json}")

    if tracer is not None:
        import json

        from repro.obs import reconcile, write_trace

        checks = reconcile.reconcile_serving(report, store, tracer=tracer)
        if plan is not None:
            checks += reconcile.reconcile_recovery(plan, tracer=tracer)
        rep = reconcile.build_report(checks)
        write_trace(args.trace, tracer)
        with open(args.trace + ".report.json", "w") as fh:
            json.dump(rep.to_dict(), fh, indent=2)
            fh.write("\n")
        c = rep.counts
        print(f"[serve] trace -> {args.trace} "
              f"(report {args.trace}.report.json: {c.get('ok', 0)} ok, "
              f"{c.get('warn', 0)} warn, {c.get('error', 0)} error)")
        for ch in rep.checks:
            if ch.level == "error":
                print(f"  [error] {ch.quantity}: {ch.message}")


if __name__ == "__main__":
    main()
