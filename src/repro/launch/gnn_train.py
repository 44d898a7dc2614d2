"""The paper's training driver: partition a graph, train a GNN distributed.

Both regimes:
  --regime fullbatch  : DistGNN-style (edge partitioning, replica sync)
  --regime minibatch  : DistDGL-style (vertex partitioning, sampling+fetch)

Usage:
  PYTHONPATH=src python -m repro.launch.gnn_train --graph OR --scale 0.05 \
      --partitioner hep100 --k 8 --model sage --regime fullbatch --epochs 5
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.core import cost_model, study
from repro.core.edge_partition import EDGE_PARTITIONERS, partition_edges
from repro.core.wire import CODECS
from repro.core.graph import paper_graph
from repro.core.metrics import edge_partition_metrics, vertex_partition_metrics
from repro.core.vertex_partition import VERTEX_PARTITIONERS, partition_vertices
from repro.fault import (FAULT_KINDS, FaultInjector, FaultPlan,
                         FaultSpecError, WorkerCrash,
                         corrupt_latest_checkpoint)
from repro.gnn.feature_store import CACHE_POLICIES
from repro.gnn.fullbatch import FullBatchTrainer
from repro.gnn.minibatch import MiniBatchTrainer
from repro.gnn.models import GNNSpec
from repro.launch.compile_cache import use_compile_cache

CRASH_EXIT = 3  # injected worker crash (distinct from real failures)


def _crash_exit(e: WorkerCrash, args) -> None:
    print(f"[gnn] FATAL: {e}")
    if args.ckpt_dir:
        print(f"[gnn] resume: re-run with --resume "
              f"(checkpoints in {args.ckpt_dir})")
    sys.exit(CRASH_EXIT)


def _mark_corrupt_handled(plan) -> None:
    """A corrupt-ckpt fault is handled once restore fell back gracefully."""
    if plan is None:
        return
    for ev in plan.fired_events():
        if ev.kind == "corrupt-ckpt":
            plan.mark_handled(ev)


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="OR", choices=["HO", "DI", "EN", "EU", "OR"])
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--partitioner", default="hep100")
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--model", default="sage", choices=["sage", "gcn", "gat"])
    ap.add_argument("--regime", default="fullbatch",
                    choices=["fullbatch", "minibatch"])
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--sync-mode", "--sync", dest="sync_mode", default="halo",
                    choices=["halo", "dense", "ring"],
                    help="full-batch sync strategy (gnn/sync.py): halo = "
                         "static-routed replica exchange, dense = global "
                         "psum baseline, ring = 1.5D ppermute block "
                         "rotation (ignores --partitioner: the blockrow "
                         "layout needs no partitioning pass)")
    ap.add_argument("--agg-backend", default="scatter",
                    choices=["scatter", "tiled", "pallas"],
                    help="aggregation backend (kernels.ops.aggregate): "
                         "data-dependent scatter, tiled segment-SpMM layout, "
                         "or the Pallas kernel (interpreted off-TPU)")
    ap.add_argument("--rebalance", action="store_true",
                    help="dynamic seed rebalancing (straggler mitigation)")
    ap.add_argument("--overlap", action="store_true",
                    help="pipelined mini-batch execution (gnn/pipeline.py): "
                         "sampling + feature prefetch for step t+1 run on a "
                         "producer thread while the device computes step t; "
                         "same batches as serial given the same seed")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="batches prepared ahead of the device step "
                         "(bounded queue; only read with --overlap)")
    ap.add_argument("--codec", default="fp32", choices=list(CODECS),
                    help="wire codec (core/wire.py) for the byte-moving "
                         "paths: replica sync + gradient all-reduce "
                         "(fullbatch) resp. feature fetch + gradient "
                         "all-reduce (minibatch). fp32 is exact; int8 adds "
                         "error feedback on gradients; variable ramps the "
                         "ratio by layer and epoch")
    ap.add_argument("--cache-policy", default="none",
                    choices=list(CACHE_POLICIES),
                    help="per-worker remote-feature cache policy (minibatch)")
    ap.add_argument("--cache-budget", type=int, default=0,
                    help="cached remote vertices per worker (minibatch)")
    ap.add_argument("--out-json", default="",
                    help="write the run's study-format row(s) here "
                         "(core/study.py serializers — same format the "
                         "benchmark drivers emit)")
    ap.add_argument("--trace", default="", metavar="PATH",
                    help="record the run's span/counter timeline to PATH "
                         "(Chrome trace-event JSON, schema gnn-trace/v1; "
                         "open in https://ui.perfetto.dev or "
                         "chrome://tracing) and write the measured-vs-"
                         "model reconciliation report to PATH.report.json")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory (ckpt/checkpoint.py: atomic "
                         "step_<n>/ dirs, keep-last-k). Saves params + "
                         "optimizer + codec EF carry + run coordinates")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint cadence: epochs (fullbatch) resp. "
                         "global steps (minibatch) between saves")
    ap.add_argument("--ckpt-keep", type=int, default=3,
                    help="complete checkpoints retained (older ones GC'd)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest complete checkpoint in "
                         "--ckpt-dir and continue from the step after it; "
                         "fp32 resume is bitwise (tests/test_fault.py)")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="SPEC",
                    help="deterministic fault injection (repeatable), "
                         "kind@key:value[,key:value...] — e.g. "
                         "crash@step:3, sample-error@step:2,worker:1, "
                         "straggler@step:1,delay:0.05, corrupt-ckpt. "
                         f"Kinds: {', '.join(FAULT_KINDS)}")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    plan, injector = None, None
    if args.inject_fault:
        try:
            plan = FaultPlan.parse(args.inject_fault, seed=args.seed)
        except FaultSpecError as e:
            print(f"[gnn] bad --inject-fault: {e}")
            sys.exit(1)
        injector = FaultInjector(plan)
        print(f"[gnn] fault plan: "
              f"{'; '.join(ev.describe() for ev in plan.events)}")

    tracer = None
    if args.trace:
        # install BEFORE anything compiles: the sync strategies report
        # their collectives when jax first traces the step function
        from repro.obs import Tracer, install
        tracer = install(Tracer())

    g = paper_graph(args.graph, scale=args.scale, seed=0)
    print(f"[gnn] graph {args.graph}: {g.num_vertices} vertices, "
          f"{g.num_edges} edges")
    rng = np.random.default_rng(args.seed)
    feats = rng.normal(size=(g.num_vertices, args.features)).astype(np.float32)
    labels = rng.integers(0, args.classes, g.num_vertices).astype(np.int32)
    train_mask = rng.random(g.num_vertices) < 0.3
    spec = GNNSpec(model=args.model, feature_dim=args.features,
                   hidden_dim=args.hidden, num_classes=args.classes,
                   num_layers=args.layers, agg_backend=args.agg_backend)

    manager = None
    if args.ckpt_dir:
        from repro.ckpt.checkpoint import CheckpointManager
        manager = CheckpointManager(args.ckpt_dir, keep=args.ckpt_keep,
                                    every=args.ckpt_every)
        if plan is not None and args.resume:
            # corrupt-ckpt: break the newest checkpoint BEFORE restore reads
            # it — restore must fall back to the previous complete one
            for ev in plan.pending("corrupt-ckpt"):
                if plan.fire(ev):
                    path = corrupt_latest_checkpoint(args.ckpt_dir)
                    print(f"[gnn] injected checkpoint corruption -> {path}")

    t0 = time.perf_counter()
    if args.regime == "fullbatch":
        partitioner = args.partitioner
        if args.sync_mode == "ring":
            # 1.5D: contiguous blockrow layout, no partitioning heuristic —
            # the near-zero partition time IS the regime's selling point
            partitioner = "blockrow"
        assert partitioner in EDGE_PARTITIONERS, (
            f"full-batch (DistGNN) uses edge partitioners: "
            f"{sorted(EDGE_PARTITIONERS)}")
        assignment = partition_edges(g, args.k, partitioner, seed=args.seed)
        pt = time.perf_counter() - t0
        m = edge_partition_metrics(g, assignment, args.k)
        print(f"[gnn] partitioned in {pt:.2f}s ({partitioner}): "
              f"rf={m.replication_factor:.2f} "
              f"edge_bal={m.edge_balance:.2f} vertex_bal={m.vertex_balance:.2f}")
        tr = FullBatchTrainer.build(
            g, assignment, args.k, spec, feats, labels, train_mask,
            sync_mode=args.sync_mode, mode="sim", seed=args.seed,
            codec=args.codec,
        )
        est = cost_model.fullbatch_epoch(tr.book, spec, codec=args.codec)
        print(f"[gnn] paper-cluster epoch estimate: {est.epoch_time*1e3:.1f} ms, "
              f"comm {est.comm_bytes.sum()/2**20:.1f} MiB "
              f"(wire {est.wire_bytes.sum()/2**20:.1f} MiB, {args.codec}), "
              f"mem max {est.memory.max()/2**20:.1f} MiB"
              + (" (OOM!)" if est.oom else ""))
        start_epoch = 0
        if manager is not None and args.resume:
            from repro.ckpt.checkpoint import checkpoint_extra
            _, extra = checkpoint_extra(args.ckpt_dir)
            tree = {"params": tr.params, "opt_state": tr.opt_state}
            if extra.get("has_ef"):
                tr.ef_state = tr._init_ef()
                tree["ef"] = tr.ef_state
            step_r, restored = manager.restore(tree)
            _mark_corrupt_handled(plan)
            if step_r is not None:
                tr.params = restored["params"]
                tr.opt_state = restored["opt_state"]
                if "ef" in restored:
                    tr.ef_state = restored["ef"]
                start_epoch = int(extra.get("epoch", step_r)) + 1
                print(f"[gnn] resumed from checkpoint epoch {step_r} "
                      f"-> continuing at epoch {start_epoch}")
            else:
                print("[gnn] --resume: no complete checkpoint found, "
                      "starting fresh")
        loss = float("nan")
        try:
            for epoch in range(start_epoch, args.epochs):
                t1 = time.perf_counter()
                if injector is not None:
                    injector.at_epoch(epoch)
                tr.set_epoch(epoch)
                loss = tr.train_step()
                print(f"[gnn] epoch {epoch:3d} loss {loss:.4f} "
                      f"({time.perf_counter()-t1:.2f}s)")
                if manager is not None:
                    tree = {"params": tr.params, "opt_state": tr.opt_state}
                    if tr.ef_state is not None:
                        tree["ef"] = tr.ef_state
                    manager.maybe_save(
                        epoch, tree,
                        extra={"epoch": epoch,
                               "has_ef": tr.ef_state is not None})
        except WorkerCrash as e:
            _crash_exit(e, args)
        if args.out_json:
            row = study.fullbatch_result_row(
                args.graph, partitioner, args.k, spec,
                metrics=m, partition_time=pt, est=est,
                sync_mode=args.sync_mode, codec=args.codec)
            row["loss"] = loss
            study.write_rows([row], args.out_json)
            print(f"[gnn] wrote study row -> {args.out_json}")
    else:
        assert args.partitioner in VERTEX_PARTITIONERS, (
            f"mini-batch (DistDGL) uses vertex partitioners: "
            f"{sorted(VERTEX_PARTITIONERS)}")
        assignment = partition_vertices(
            g, args.k, args.partitioner, seed=args.seed, train_mask=train_mask)
        pt = time.perf_counter() - t0
        m = vertex_partition_metrics(g, assignment, args.k, train_mask)
        print(f"[gnn] partitioned in {pt:.2f}s: edge_cut={m.edge_cut:.3f} "
              f"vertex_bal={m.vertex_balance:.2f}")
        steps_per_epoch = max(int(train_mask.sum()) // args.batch, 1)
        start_epoch, step_offset, next_step = 0, 0, 0
        resume_extra = None
        if manager is not None and args.resume:
            from repro.ckpt.checkpoint import checkpoint_extra
            gstep, resume_extra = checkpoint_extra(args.ckpt_dir)
            if gstep is not None:
                next_step = gstep + 1          # first global step to draw
                start_epoch = next_step // steps_per_epoch
                step_offset = next_step % steps_per_epoch
        tr = MiniBatchTrainer.build(
            g, assignment, args.k, spec, feats, labels, train_mask,
            global_batch=args.batch, seed=args.seed, rebalance=args.rebalance,
            cache_policy=args.cache_policy, cache_budget=args.cache_budget,
            overlap=args.overlap, prefetch_depth=args.prefetch_depth,
            codec=args.codec, start_step=next_step, injector=injector,
        )
        if manager is not None and args.resume:
            tree = {"params": tr.params, "opt_state": tr.opt_state}
            if resume_extra and resume_extra.get("has_ef"):
                tr.ef_state = tr._init_ef()
                tree["ef"] = tr.ef_state
            step_r, restored = manager.restore(tree)
            _mark_corrupt_handled(plan)
            if step_r is not None:
                tr.params = restored["params"]
                tr.opt_state = restored["opt_state"]
                if "ef" in restored:
                    tr.ef_state = restored["ef"]
                print(f"[gnn] resumed from checkpoint step {step_r} -> "
                      f"continuing at global step {next_step} "
                      f"(epoch {start_epoch}, step {step_offset})")
            else:
                print("[gnn] --resume: no complete checkpoint found, "
                      "starting fresh")
        if args.cache_budget:
            print(f"[gnn] feature cache: policy={args.cache_policy} "
                  f"budget={args.cache_budget}/worker "
                  f"(filled {tr.store.cache_sizes.tolist()})")
        sms, losses = [], []
        all_sms = []  # every traced step (the fetch counters span all epochs)
        gstep = next_step
        try:
            for epoch in range(start_epoch, args.epochs):
                t1 = time.perf_counter()
                tr.set_epoch(epoch)
                losses, remotes, hit_rates = [], [], []
                sms = []
                first = step_offset if epoch == start_epoch else 0
                for step in range(first, steps_per_epoch):
                    sm = tr.train_step()
                    sms.append(sm)
                    all_sms.append(sm)
                    losses.append(sm.loss)
                    remotes.append(sm.remote_vertices.sum())
                    hit_rates.append(sm.hit_rate)
                    if manager is not None:
                        tree = {"params": tr.params,
                                "opt_state": tr.opt_state}
                        if tr.ef_state is not None:
                            tree["ef"] = tr.ef_state
                        manager.maybe_save(
                            gstep, tree,
                            extra={"epoch": epoch, "step": step,
                                   "has_ef": tr.ef_state is not None})
                    gstep += 1
                est = cost_model.minibatch_step(
                    sm.input_vertices, sm.remote_vertices, sm.edges,
                    tr.book.sizes, spec,
                    remote_miss_vertices=sm.remote_misses,
                    cached_vertices=tr.store.cache_sizes, codec=args.codec)
                overlap_note = ""
                if args.overlap:
                    eff = np.mean([s.overlap_efficiency for s in sms])
                    overlap_note = f"overlap_eff {eff:.2f} "
                print(f"[gnn] epoch {epoch:3d} loss {np.mean(losses):.4f} "
                      f"remote/step {np.mean(remotes):.0f} "
                      f"hit_rate {np.mean(hit_rates):.2f} "
                      f"{overlap_note}"
                      f"cluster step est {est.step_time*1e3:.1f} ms "
                      f"({time.perf_counter()-t1:.2f}s)")
        except WorkerCrash as e:
            tr.close()
            _crash_exit(e, args)
        tr.close()
        if args.out_json and not sms:
            print("[gnn] --out-json needs at least one trained epoch; "
                  "no row written")
        elif args.out_json:
            # average the LAST epoch's measured per-worker metrics (same
            # aggregation as study.minibatch_row) and re-estimate from them
            inputs = np.stack([s.input_vertices for s in sms]).mean(axis=0)
            remote = np.stack([s.remote_vertices for s in sms]).mean(axis=0)
            edges = np.stack([s.edges for s in sms]).mean(axis=0)
            hits = np.stack([s.cache_hits for s in sms]).mean(axis=0)
            misses = np.stack([s.remote_misses for s in sms]).mean(axis=0)
            est = cost_model.minibatch_step(
                inputs, remote, edges, tr.book.sizes, spec,
                seeds_per_worker=max(args.batch // args.k, 1),
                remote_miss_vertices=misses,
                cached_vertices=tr.store.cache_sizes, codec=args.codec)
            row = study.minibatch_result_row(
                args.graph, args.partitioner, args.k, spec,
                metrics=m, partition_time=pt, batch=args.batch,
                inputs=inputs, remote=remote, hits=hits, misses=misses,
                est=est, steps_per_epoch=steps_per_epoch,
                cache_policy=args.cache_policy,
                cache_budget=args.cache_budget,
                overlap=args.overlap, prefetch_depth=args.prefetch_depth,
                host_times=study.host_phase_means(sms), codec=args.codec)
            row["loss"] = float(np.mean(losses))
            study.write_rows([row], args.out_json)
            print(f"[gnn] wrote study row -> {args.out_json}")

    if tracer is not None:
        import json

        from repro.obs import reconcile, write_trace

        if args.regime == "fullbatch":
            checks = reconcile.reconcile_fullbatch(tr, tracer=tracer)
        else:
            checks = reconcile.reconcile_minibatch(tr, all_sms,
                                                   tracer=tracer)
        if plan is not None:
            checks += reconcile.reconcile_recovery(plan, tracer=tracer)
        report = reconcile.build_report(checks)
        write_trace(args.trace, tracer)
        with open(args.trace + ".report.json", "w") as fh:
            json.dump(report.to_dict(), fh, indent=2)
            fh.write("\n")
        c = report.counts
        print(f"[gnn] trace -> {args.trace} "
              f"(report {args.trace}.report.json: {c.get('ok', 0)} ok, "
              f"{c.get('warn', 0)} warn, {c.get('error', 0)} error)")
        for ch in report.checks:
            if ch.level == "error":
                print(f"  [error] {ch.quantity}: {ch.message}")


if __name__ == "__main__":
    main()
