#!/usr/bin/env python3
"""Smoke run of the GNN trainers on TPU: the quickest proof that the main
training path still starts and computes on the chip.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip mesh path only

One chip, each phase a few steps at the paper grid's widths (F = H = 512,
3 layers, 16 classes) through the entry points `repro.launch.gnn_train` uses
(paper_graph -> partition_* -> *Trainer.build -> train_step):

  kernel  segment_spmm sum and max at F = 512, many edge blocks per row
          tile, against the scatter oracle on the same chip
  a       full-batch sage, k = 1, tiled backend (the Pallas kernel), against
          the same step on the scatter backend
  b       full-batch GAT (4 heads), tiled: the max combiner, against scatter
  c       mini-batch sage, k = 4 simulated, metis, overlapped pipeline: the
          step that donates its parameter buffers
  cli     one in-process `gnn_train.main` call with a short argv

Four chips: full-batch sage k = 4 with halo (hep100) and with ring under
`mode="shard_map"` on a ("parts",) mesh, against `mode="sim"` and against
the k = 1 oracle.

The readings printed on the way are smoke readings, not benchmark
numbers: compile seconds, step seconds around `block_until_ready`, the
compiled step's temporary bytes (its memory analysis), and the device's
`peak_bytes_in_use` since the process started, which on a v5e counts the
arrays the process held but not a step's temporaries. Kernel and oracle are compared
on the same chip, since the TPU's default f32 matmul precision is not the
CPU's. The last line of standard output is one JSON object naming the
device. Without a TPU the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
FEATURES = HIDDEN = 512
LAYERS = 3
CLASSES = 16
STEPS = 5
# Adam's step size. At width 512 the trainers' default 1e-2, and 1e-3 too,
# first raise the loss (on a v5e, sage at scale 1.0 went 3.21 -> 3.48 ->
# 3.01 at 1e-3); at 1e-4 sage (OR 0.1) and GAT (OR 0.25) fall from the
# first step on the CPU.
LR = 1e-4
# Graph scales (OR, paper_graph) whose tiled train step fits one v5e's HBM:
# compiled for a described v5e, the sage step at 1.0 needs 13.3 GiB of
# temporaries and the 4-head GAT step at 0.25 needs 11.2 GiB.
SAGE_SCALE = 1.0
GAT_SCALE = 0.25
MINIBATCH_SCALE = 1.0
MINIBATCH_BATCH = 1024
# The k = 4 "sim" comparison vmaps all four partitions onto one chip: the
# halo step at 0.5 needs 17 GiB there, at 0.25 it needs 7.3 GiB.
MESH_SCALE = 0.25
# The kernel's f32 one-hot matmul may round messages to bf16 (relative
# error 2^-9 each); a row's sum may then be off by 2^-9 * sum(|m|). The
# bound below allows twice that.
SUM_ROUNDING = 2.0 ** -8
# End-to-end, the aggregates' rounding passes through 3 layers of matmuls;
# kernel and oracle must agree to this fraction of the oracle's magnitude.
E2E_REL_TOL = 2.0 ** -5


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, what) -> None:
    """A failed check ends the run with a traceback (asserts vanish under
    `python -O`)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def node_data(g):
    """Seeded features, labels a linear probe of the features can learn
    (so a few steps must lower the loss), and a 30% training mask."""
    rng = np.random.default_rng(SEED)
    feats = rng.normal(size=(g.num_vertices, FEATURES)).astype(np.float32)
    labels = np.argmax(feats[:, :CLASSES], axis=1).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    return feats, labels, train


def spec_for(model: str, backend: str):
    from repro.gnn.models import GNNSpec

    return GNNSpec(model=model, feature_dim=FEATURES, hidden_dim=HIDDEN,
                   num_classes=CLASSES, num_layers=LAYERS, agg_backend=backend)


def device_bytes(device, key: str = "bytes_in_use") -> int:
    return int(device.memory_stats()[key])


def peak_bytes() -> int:
    import jax

    return device_bytes(jax.devices()[0], "peak_bytes_in_use")


def check_losses(name: str, losses) -> None:
    check(np.all(np.isfinite(losses)), (name, losses))
    check(losses[-1] < losses[0], (name, "loss did not fall", losses))


def check_kernel_ran(name: str, hlo: str) -> None:
    n = hlo.count("tpu_custom_call")
    check(n > 0, f"{name}: no Pallas kernel in the compiled step")
    say(f"{name}: {n} tpu_custom_call in the compiled step")


def train_fullbatch(g, data, spec, k, *, sync_mode="halo",
                    partitioner="random", mode="sim", mesh=None,
                    steps=STEPS):
    """Build a FullBatchTrainer as gnn_train does and take `steps` steps.
    Returns (trainer, initial global logits, losses, compiled step HLO)."""
    import jax

    from repro.core.edge_partition import partition_edges
    from repro.gnn.fullbatch import FullBatchTrainer

    feats, labels, train = data
    assignment = (None if sync_mode == "ring"
                  else partition_edges(g, k, partitioner, seed=SEED))
    tr = FullBatchTrainer.build(g, assignment, k, spec, feats, labels, train,
                                sync_mode=sync_mode, mode=mode, mesh=mesh,
                                seed=SEED, lr=LR)
    logits = tr.forward_logits_global()
    t0 = time.perf_counter()
    compiled = tr._train_step.lower(tr.params, tr.opt_state,
                                    tr.blocks).compile()
    compile_s = time.perf_counter() - t0
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(tr.train_step())
        jax.block_until_ready(tr.params)
        step_s.append(time.perf_counter() - t0)
    temp = compiled.memory_analysis().temp_size_in_bytes
    say(f"{spec.model}/{spec.agg_backend} k={k} {sync_mode}/{mode}: "
        f"compile_s={compile_s:.3f} step_s={[round(s, 4) for s in step_s]} "
        f"losses={losses} step_temp_bytes={temp} peak_bytes={peak_bytes()} "
        f"(smoke reading)")
    return tr, logits, losses, compiled.as_text()


def compare(name: str, got_logits, got_loss, ref_logits, ref_loss) -> None:
    """Kernel path against oracle path on the same chip."""
    d_logits = float(np.abs(got_logits - ref_logits).max())
    d_loss = abs(got_loss - ref_loss)
    tol_logits = E2E_REL_TOL * float(np.abs(ref_logits).max())
    tol_loss = E2E_REL_TOL * abs(ref_loss)
    say(f"{name}: max|d logits|={d_logits!r} (tol {tol_logits!r}) "
        f"|d first-step loss|={d_loss!r} (tol {tol_loss!r})")
    check(d_logits <= tol_logits and d_loss <= tol_loss, name)


def phase_kernel() -> None:
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    v, e = 4096, 131072
    dst = rng.integers(0, v, e).astype(np.int32)
    order, ldst, rows = ops.prepare_tiled_edges(dst, v)
    n_tiles = rows // ops.DEFAULT_TILE_V
    blocks = order.shape[0] // n_tiles // ops.DEFAULT_BLOCK_E
    say(f"kernel: F={FEATURES} rows={rows} edge blocks per row tile="
        f"{blocks}")
    check(blocks > 1, "one edge block per row tile")
    msgs = rng.normal(size=(e, FEATURES)).astype(np.float32)
    ldst = jnp.asarray(ldst)
    for combiner, fill in (("sum", 0.0), ("max", -np.inf)):
        pad = jnp.asarray(np.concatenate(
            [msgs, np.full((1, FEATURES), fill, np.float32)])[order])
        got = np.asarray(ops.segment_spmm(pad, ldst, rows, combiner=combiner))
        want = np.asarray(ops.segment_spmm(pad, ldst, rows, combiner=combiner,
                                           use_pallas=False))
        if combiner == "max":
            check(np.array_equal(np.isinf(got), np.isinf(want)),
                  "kernel max: empty rows differ")
            fin = np.isfinite(want)
            diff = float(np.abs(got[fin] - want[fin]).max())
            say(f"kernel: max max|kernel - oracle|={diff!r} (tol 0.0)")
            check(diff == 0.0, "kernel max differs from the oracle")
            continue
        bound = SUM_ROUNDING * np.asarray(ops.segment_spmm(
            jnp.abs(pad), ldst, rows, use_pallas=False))
        diff = np.abs(got - want)
        ratio = float((diff / np.maximum(bound, 1e-30)).max())
        say(f"kernel: sum max|kernel - oracle|={float(diff.max())!r} "
            f"max diff/bound={ratio!r} (tol 1.0)")
        check(np.all(diff <= bound), "kernel sum outside its rounding bound")


def phase_fullbatch(model: str, scale: float) -> None:
    from repro.core.graph import paper_graph
    from repro.kernels.tiling import DEFAULT_BLOCK_E, tiled_shape

    g = paper_graph("OR", scale=scale, seed=SEED)
    say(f"{model}: OR scale={scale} V={g.num_vertices} E={g.num_edges}")
    data = node_data(g)
    tr, logits, losses, hlo = train_fullbatch(
        g, data, spec_for(model, "tiled"), 1)
    _, n_tiles = tiled_shape(tr.book.v_max + 1)
    blocks = tr.book.agg_order.shape[1] // n_tiles // DEFAULT_BLOCK_E
    say(f"{model}: edge blocks per row tile={blocks}")
    check(blocks > 1, f"{model}: one edge block per row tile")
    check_kernel_ran(model, hlo)
    check_losses(model, losses)
    del tr
    gc.collect()
    ref, ref_logits, ref_losses, _ = train_fullbatch(
        g, data, spec_for(model, "scatter"), 1, steps=1)
    del ref
    gc.collect()
    compare(f"{model} tiled vs scatter", logits, losses[0], ref_logits,
            ref_losses[0])


def phase_minibatch() -> None:
    import jax

    from repro.core.graph import paper_graph
    from repro.core.vertex_partition import partition_vertices
    from repro.gnn.minibatch import MiniBatchTrainer

    g = paper_graph("OR", scale=MINIBATCH_SCALE, seed=SEED)
    feats, labels, train = node_data(g)
    assignment = partition_vertices(g, 4, "metis", seed=SEED,
                                    train_mask=train)
    tr = MiniBatchTrainer.build(
        g, assignment, 4, spec_for("sage", "tiled"), feats, labels, train,
        global_batch=MINIBATCH_BATCH, seed=SEED, lr=LR, overlap=True)
    try:
        donated = jax.tree.leaves(tr.params)[0]
        losses, step_s = [], []
        for _ in range(STEPS):
            t0 = time.perf_counter()
            losses.append(tr.train_step().loss)
            jax.block_until_ready(tr.params)
            step_s.append(time.perf_counter() - t0)
    finally:
        tr.close()
    say(f"minibatch sage/tiled k=4 metis overlap batch={MINIBATCH_BATCH}: "
        f"step_s={[round(s, 4) for s in step_s]} (first includes compile) "
        f"losses={losses} peak_bytes={peak_bytes()} (smoke reading)")
    check(donated.is_deleted(), "the step did not donate its parameters")
    say("minibatch: the step donated its parameter buffers")
    check_losses("minibatch", losses)


def phase_cli() -> None:
    from repro.launch import gnn_train

    gnn_train.main([
        "--graph", "OR", "--scale", "0.02", "--k", "1",
        "--partitioner", "random", "--model", "sage", "--agg-backend",
        "tiled", "--features", str(FEATURES), "--hidden", str(HIDDEN),
        "--layers", str(LAYERS), "--epochs", "2",
    ])


def check_placement(tr, devices) -> None:
    """Each chip holds exactly its quarter of the stacked blocks."""
    import jax

    per_device = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(tr.blocks):
        shards = leaf.addressable_shards
        check(sorted(s.device.id for s in shards)
              == sorted(d.id for d in devices), leaf.shape)
        for s in shards:
            check(s.data.shape[0] == 1, (leaf.shape, s.data.shape))
            per_device[s.device] += s.data.nbytes
    total = sum(per_device.values())
    in_use = {d.id: device_bytes(d) for d in devices}
    say(f"mesh: block bytes per chip={[per_device[d] for d in devices]} "
        f"of {total}; bytes_in_use per chip={in_use}")
    for d in devices:
        check(per_device[d] * len(devices) == total,
              f"chip {d.id} holds {per_device[d]} of {total} block bytes")
        check(in_use[d.id] >= per_device[d],
              f"chip {d.id} reports fewer bytes in use than its blocks")


def ring_aggregate_hlo(tr, mesh) -> str:
    """One ring aggregate at F = 512, compiled on the real mesh."""
    import jax
    import jax.numpy as jnp

    from repro.gnn.fullbatch import AXIS, wrap_spmd
    from repro.gnn.sync import make_sync

    def agg(_, blk):
        sync = make_sync("ring", blk, tr.book.num_vertices, AXIS)
        return sync.edge_aggregate(blk, blk.x, lambda s, d, m: s * m[:, None],
                                   backend=tr.spec.agg_backend)

    fn = jax.jit(wrap_spmd(agg, tr.book.k, "shard_map", mesh))
    return fn.lower(jnp.zeros(()), tr.blocks).compile().as_text()


def phase_mesh() -> None:
    import jax

    from repro.analysis import collective_bytes_from_hlo
    from repro.core.graph import paper_graph
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    k = len(devices)
    check(k == 4, f"--chips 4 needs four devices, found {k}")
    mesh = make_mesh((k,), ("parts",))
    g = paper_graph("OR", scale=MESH_SCALE, seed=SEED)
    say(f"mesh: OR scale={MESH_SCALE} V={g.num_vertices} E={g.num_edges}")
    data = node_data(g)
    spec = spec_for("sage", "tiled")
    tr, ref_logits, ref_losses, _ = train_fullbatch(g, data, spec, 1)
    del tr
    gc.collect()
    # shard_map runs before the vmap simulation: the one four-chip run so
    # far hung the chip in the first k = 4 sim (halo) train step
    for sync, part in (("halo", "hep100"), ("ring", "random")):
        tr, logits, losses, hlo = train_fullbatch(
            g, data, spec, k, sync_mode=sync, partitioner=part,
            mode="shard_map", mesh=mesh)
        check_placement(tr, devices)
        check_kernel_ran(f"{sync} shard_map", hlo)
        counts = collective_bytes_from_hlo(hlo)["count_per_kind"]
        say(f"{sync} shard_map step collectives: {counts}")
        if sync == "halo":
            check(counts.get("all-to-all", 0) > 0, counts)
        else:
            one = collective_bytes_from_hlo(
                ring_aggregate_hlo(tr, mesh))["count_per_kind"]
            say(f"ring: one aggregate compiles to {one}")
            check(one.get("collective-permute", 0) == k - 1, one)
        check_losses(f"{sync} shard_map", losses)
        compare(f"{sync} shard_map vs k=1", logits, losses[0], ref_logits,
                ref_losses[0])
        del tr
        gc.collect()
        tr, sim_logits, sim_losses, _ = train_fullbatch(
            g, data, spec, k, sync_mode=sync, partitioner=part)
        del tr
        gc.collect()
        compare(f"{sync} shard_map vs sim", logits, losses[0], sim_logits,
                sim_losses[0])
        d_traj = float(np.abs(np.subtract(losses, sim_losses)).max())
        say(f"{sync}: max |loss shard_map - loss sim| over {STEPS} steps="
            f"{d_traj!r}")
        check(d_traj <= E2E_REL_TOL * abs(sim_losses[0]),
              f"{sync}: shard_map and sim loss trajectories differ")


def main(argv=None) -> None:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the shard_map mesh phase")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax found {dev.platform!r})")
    say(f"device {dev.device_kind} x{len(jax.devices())}")
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"compile cache {cache}: {warm} entries at start")
    if args.chips == 4:
        phase_mesh()
    else:
        phase_kernel()
        phase_fullbatch("sage", SAGE_SCALE)
        phase_fullbatch("gat", GAT_SCALE)
        phase_minibatch()
        phase_cli()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
