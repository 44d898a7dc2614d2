"""Assignment §Roofline: three-term roofline per (arch x shape) on the
single-pod 16x16 mesh, read from the dry-run cache (dryrun_results.json) —
plus the GNN aggregation-backend bench: measured scatter-vs-tiled
segment-reduce (sum AND max) microbench rows, and scatter-vs-tiled step time
+ aggregate traffic bytes for the full-batch (sage/gcn/gat, k in {1, 4}) and
mini-batch (sage) trainers — gat exercises the segment-max path end to end —
and the serial-vs-pipelined mini-batch step rows (the overlapped execution
engine, gnn/pipeline.py, sharing fig19's measured bench), and the
ring-vs-halo-vs-dense sync-strategy step rows (gnn/sync.py; the full k
sweep + HLO byte pin is fig_ring_scaleout).
`--smoke` (or `run.py --smoke`) runs the aggregation bench at the trimmed CI
scale; the dry-run section still needs the cache.
"""

import json
import os
import sys
import time

import numpy as np

from benchmarks.common import emit

# the agg bench sizes itself independently of common.SCALE so a direct
# `python benchmarks/roofline.py --smoke` is CI-fast without env setup
AGG_SCALE = float(os.environ.get("BENCH_SCALE", "0.02"))

RESULTS = os.environ.get(
    "DRYRUN_RESULTS",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "dryrun_results.json"))


def _agg_traffic_bytes(book, spec, backend) -> str:
    """Analytic per-step aggregate traffic (all partitions, fwd only):
    message bytes streamed through the aggregation. The scatter backend
    reads/writes the raw symmetrised edge list; the tiled backend streams
    the blocked layout (real edges + tile padding; its book carries the
    layout — the scatter book is built without one). sage/gcn stream one
    [E, hidden] sum per layer; gat streams two [E, heads] score reduces
    (segment-max + den sum) plus the [E, hidden] num sum."""
    width = spec.hidden_dim
    if spec.model == "gat":
        width += 2 * spec.gat_heads
    e2 = 2 * int(book.emask.sum())          # real symmetrised edges
    if backend == "scatter":
        return f"agg_bytes={spec.num_layers * 2 * e2 * width * 4}"
    e_tiled = int(np.prod(book.agg_order.shape))
    return (f"agg_bytes={spec.num_layers * 2 * e_tiled * width * 4};"
            f"tiled_pad_frac={1.0 - e2 / max(e_tiled, 1):.3f}")


def _time_steps(step_fn, reps: int = 3) -> float:
    step_fn()  # compile + warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        step_fn()
        best = min(best, time.perf_counter() - t0)
    return best


def segment_reduce_bench() -> None:
    """Measured scatter-vs-tiled segment-reduce rows, one per combiner:
    the kernel-level proof that BOTH the sum (GNN neighbor aggregation) and
    the max (GAT softmax stabilisation) run without a data-dependent
    scatter under the tiled backend."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    v = max(int(65536 * AGG_SCALE / 0.02), 1024)
    e, f = 16 * v, 64
    dst = rng.integers(0, v, e).astype(np.int32)
    msgs = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    order, ldst, _ = ops.prepare_tiled_edges(dst, v)
    jdst = jnp.asarray(dst)
    order, ldst = jnp.asarray(order), jnp.asarray(ldst)
    for reduce in ("sum", "max"):
        times = {}
        for backend in ("scatter", "tiled"):
            kw = ({} if backend == "scatter"
                  else {"edge_order": order, "local_dst": ldst})
            fn = jax.jit(lambda m, bk=backend, rd=reduce, kw=kw: ops.aggregate(
                m, jdst, v, backend=bk, reduce=rd, **kw))
            times[backend] = _time_steps(
                lambda: jax.block_until_ready(fn(msgs)))
            emit(f"roofline.agg.segreduce.{reduce}.{backend}",
                 times[backend], f"edges={e};rows={v};feat={f}")
        emit(f"roofline.agg.segreduce.{reduce}.speedup", 0.0,
             f"scatter_over_tiled={times['scatter'] / times['tiled']:.3f}")


def agg_backend_bench() -> None:
    """Measured scatter-vs-tiled step time (the tentpole's proof row);
    gat additionally runs its softmax max through the tiled segment-max."""
    import dataclasses

    from repro.core.edge_partition import partition_edges
    from repro.core.graph import paper_graph
    from repro.core.vertex_partition import partition_vertices
    from repro.gnn.fullbatch import FullBatchTrainer
    from repro.gnn.minibatch import MiniBatchTrainer
    from repro.gnn.models import GNNSpec

    g = paper_graph("OR", scale=AGG_SCALE, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 32)).astype(np.float32)
    labels = rng.integers(0, 8, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3

    for model in ("sage", "gcn", "gat"):
        spec = GNNSpec(model=model, feature_dim=32, hidden_dim=32,
                       num_classes=8, num_layers=2)
        for k in (1, 4):
            asg = (np.zeros(g.num_edges, np.int32) if k == 1
                   else partition_edges(g, k, "hep100", seed=0))
            times = {}
            for backend in ("scatter", "tiled"):
                tr = FullBatchTrainer.build(
                    g, asg, k, dataclasses.replace(spec, agg_backend=backend),
                    feats, labels, train, seed=0)
                times[backend] = _time_steps(tr.train_step)
                emit(f"roofline.agg.fullbatch.{model}.k{k}.{backend}",
                     times[backend],
                     f"{_agg_traffic_bytes(tr.book, spec, backend)};"
                     f"edges={g.num_edges}")
            emit(f"roofline.agg.fullbatch.{model}.k{k}.speedup", 0.0,
                 f"scatter_over_tiled={times['scatter'] / times['tiled']:.3f}")

    spec = GNNSpec(model="sage", feature_dim=32, hidden_dim=32,
                   num_classes=8, num_layers=2)
    owner = partition_vertices(g, 4, "metis", seed=0)
    times = {}
    for backend in ("scatter", "tiled"):
        tr = MiniBatchTrainer.build(
            g, owner, 4, dataclasses.replace(spec, agg_backend=backend),
            feats, labels, train, global_batch=256, seed=0)
        tr.train_step()  # compile
        metrics = [tr.train_step() for _ in range(3)]
        times[backend] = min(m.compute_time_host for m in metrics)
        emit(f"roofline.agg.minibatch.sage.k4.{backend}", times[backend],
             f"edges_per_step={int(metrics[-1].edges.sum())}")
    emit("roofline.agg.minibatch.sage.k4.speedup", 0.0,
         f"scatter_over_tiled={times['scatter'] / times['tiled']:.3f}")


def sync_mode_bench() -> None:
    """Measured ring-vs-halo-vs-dense step time at one k (the SyncStrategy
    seam end to end, same trainer): the per-aggregate collective volume of
    each mode rides along so the step-time ordering can be read against the
    bytes ordering. The full k sweep lives in fig_ring_scaleout."""
    from repro.core.edge_partition import partition_edges
    from repro.core.graph import paper_graph
    from repro.gnn.fullbatch import FullBatchTrainer
    from repro.gnn.models import GNNSpec
    from repro.gnn.sync import sync_bytes_per_round, sync_wire_bytes_per_round

    g = paper_graph("OR", scale=AGG_SCALE, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 32)).astype(np.float32)
    labels = rng.integers(0, 8, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    spec = GNNSpec(model="sage", feature_dim=32, hidden_dim=32,
                   num_classes=8, num_layers=2)
    k = 4
    asg = partition_edges(g, k, "hep100", seed=0)
    times = {}
    for mode in ("ring", "halo", "dense"):
        tr = FullBatchTrainer.build(
            g, None if mode == "ring" else asg, k, spec,
            feats, labels, train, sync_mode=mode, seed=0)
        times[mode] = _time_steps(tr.train_step)
        emit(f"roofline.sync.fullbatch.sage.k{k}.{mode}", times[mode],
             f"codec=fp32;"
             f"round_bytes={sync_bytes_per_round(tr.book, spec.hidden_dim, mode)};"
             f"wire_bytes={sync_wire_bytes_per_round(tr.book, spec.hidden_dim, mode)}")
    # the compressed-wire point: same ring step trained through the int8+EF
    # codec — the wire column shrinks ~4x while round_bytes stays logical
    tr8 = FullBatchTrainer.build(
        g, None, k, spec, feats, labels, train,
        sync_mode="ring", seed=0, codec="int8")
    t8 = _time_steps(tr8.train_step)
    emit(f"roofline.sync.fullbatch.sage.k{k}.ring_int8", t8,
         f"codec=int8;"
         f"round_bytes={sync_bytes_per_round(tr8.book, spec.hidden_dim, 'ring')};"
         f"wire_bytes={sync_wire_bytes_per_round(tr8.book, spec.hidden_dim, 'ring', codec='int8')}")
    emit(f"roofline.sync.fullbatch.sage.k{k}.speedup", 0.0,
         f"halo_over_ring={times['halo'] / times['ring']:.3f};"
         f"dense_over_ring={times['dense'] / times['ring']:.3f}")


def overlap_bench() -> None:
    """Measured serial-vs-pipelined mini-batch step rows (the overlapped
    execution engine, gnn/pipeline.py) — shares fig19's bench so the two
    smoke artifacts can't drift apart."""
    from benchmarks.fig19_phase_times import measure_overlap

    m = measure_overlap(AGG_SCALE)
    for mode in ("serial", "pipelined"):
        r = m[mode]
        emit(f"roofline.overlap.minibatch.sage.k{m['k']}.{mode}", r["wall"],
             f"host={r['sample']+r['fetch']+r['transfer']:.4f}s;"
             f"compute={r['compute']:.4f}s;"
             f"overlap_eff={r['overlap_efficiency']:.2f}")
    emit(f"roofline.overlap.minibatch.sage.k{m['k']}.speedup", 0.0,
         f"serial_over_pipelined={m['speedup']:.3f};"
         f"losses_identical={m['losses_identical']}")


def serving_bench() -> None:
    """Measured serve-step rows (scatter vs tiled): the online micro-batch
    path — embedding-store gather + final-layer recompute through
    `ops.aggregate` — alongside the modeled cluster service time. The
    layer-wise offline pass is timed too (host, per layer)."""
    import dataclasses

    from repro.core.partition_book import build_vertex_book
    from repro.core.vertex_partition import partition_vertices
    from repro.core.graph import paper_graph
    from repro.gnn.inference import (
        LayerwiseInference,
        edge_assignment_from_vertex,
    )
    from repro.gnn.models import GNNSpec, init_params
    from repro.serve import build_serving

    g = paper_graph("OR", scale=AGG_SCALE, seed=0)
    rng = np.random.default_rng(0)
    spec0 = GNNSpec(model="sage", feature_dim=32, hidden_dim=32,
                    num_classes=8, num_layers=2)
    feats = rng.normal(size=(g.num_vertices, 32)).astype(np.float32)
    owner = partition_vertices(g, 4, "metis", seed=0)
    vbook = build_vertex_book(g, owner, 4)
    ids = rng.integers(0, g.num_vertices, 32)

    times = {}
    for backend in ("scatter", "tiled"):
        spec = dataclasses.replace(spec0, agg_backend=backend)
        params = init_params(spec, seed=0)
        eng = LayerwiseInference.build(
            g, edge_assignment_from_vertex(g, owner), 4, spec, params, feats)
        embeddings = eng.run()
        emit(f"roofline.serve.layerwise.{backend}", sum(eng.layer_times),
             f"layers={spec.num_layers};"
             f"halo_bytes={eng.sync_bytes()}")
        engines, batchers, _ = build_serving(
            g, vbook, spec, params, embeddings, hops=1, fanout=10,
            max_batch=32, cache_policy="degree",
            cache_budget=max(g.num_vertices // 10, 1))
        batch = batchers[0].build_mfg(ids)
        _, stats, _ = engines[0].answer(batch)  # compile + warm
        times[backend] = _time_steps(lambda: engines[0].answer(batch))
        est = engines[0].estimate(batch, stats)
        emit(f"roofline.serve.microbatch.sage.{backend}", times[backend],
             f"batch=32;edges={batch.num_edges};"
             f"miss_bytes={stats.miss_bytes};"
             f"model_service_us={est.service_time*1e6:.0f}")
    emit("roofline.serve.microbatch.sage.speedup", 0.0,
         f"scatter_over_tiled={times['scatter'] / times['tiled']:.3f}")


def main() -> None:
    smoke = "--smoke" in sys.argv or os.environ.get("BENCH_FAST") == "1"
    if smoke:
        segment_reduce_bench()
        agg_backend_bench()
        sync_mode_bench()
        overlap_bench()
        serving_bench()
    if not os.path.exists(RESULTS):
        emit("roofline.missing", 0.0,
             "run `python -m repro.launch.dryrun --all --both-meshes` first")
        return
    with open(RESULTS) as f:
        results = json.load(f)
    cells = {k: v for k, v in sorted(results.items())
             if "error" not in v and v.get("mesh") == "16x16"}
    fits = 0
    for key, v in cells.items():
        r = v["roofline"]
        peak_gib = v["bytes_per_device"]["peak"] / 2**30
        fits += peak_gib <= 16.0
        # optimized §Perf variants are stored under "...|<strategy>" keys
        variant = ".{}".format(key.split("|")[3]) if key.count("|") >= 3 else ""
        emit(
            f"roofline.{v['arch']}.{v['shape']}{variant}", r["bound_s"],
            f"dom={r['dominant']};c_ms={r['compute_s']*1e3:.2f};"
            f"m_ms={r['memory_s']*1e3:.2f};n_ms={r['collective_s']*1e3:.2f};"
            f"mfu_bound={r['mfu_bound']:.3f};"
            f"useful={r['useful_flops_ratio']:.3f};"
            f"peak_GiB={peak_gib:.2f};"
            f"hlo_coll_ms={v['roofline_hlo']['collective_s']*1e3:.2f}",
        )
    multi = {k: v for k, v in results.items()
             if "error" not in v and v.get("mesh") == "2x16x16"}
    emit("roofline.summary", 0.0,
         f"single_pod_cells={len(cells)};fits_16GiB={fits};"
         f"multi_pod_cells={len(multi)};"
         f"multi_pod_ok={sum(1 for v in multi.values() if 'error' not in v)}")


if __name__ == "__main__":
    main()
