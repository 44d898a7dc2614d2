"""Ring vs halo vs dense scale-out (the 1.5D axis the paper leaves out).

For each k: measured full-batch step time under all three sync strategies
(vmap-sim, same trainer), analytic per-aggregate collective bytes, and the
ring's COMPILED collective-permute bytes (subprocess shard_map over k host
devices, parsed with launch/hlo.py) pinned against `ring_bytes_per_round`.

Claims checked per k in the smoke:
  * ring HLO bytes == analytic k·(k−1)·(Vb+1)·d·4 (exactly k−1 permutes)
  * ring bytes < DenseSync's O(V·d) at every k
  * blockrow partition time is near-zero (no heuristic pass)

`--out-json` / `--out-csv` write the study-format rows + the printed CSV —
the CI artifacts. `--smoke` (or run.py --smoke / BENCH_FAST=1) keeps the
trimmed grid.
"""

import argparse
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from benchmarks.common import FAST, KS, SCALE, cache, emit
from repro.core import cost_model
from repro.core.study import fullbatch_result_row, write_rows
from repro.gnn.fullbatch import FullBatchTrainer
from repro.gnn.models import GNNSpec

GRAPH = "OR"
HALO_METHOD = "hep100"
# standalone `--smoke` runs the trimmed scale without env setup, same
# convention as fig_serving (run.py --smoke sets BENCH_FAST for the suite)
SMOKE = FAST or "--smoke" in sys.argv
RING_SCALE = float(os.environ.get("BENCH_SCALE", "0.02")) if SMOKE else SCALE


def _time_steps(step_fn, reps: int = 3) -> float:
    step_fn()  # compile + warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        step_fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _ring_hlo_bytes(k: int, d: int, scale: float) -> tuple[int, int]:
    """(permute_count, per_device_bytes) of ONE compiled ring aggregate,
    measured from real shard_map HLO over k host devices (subprocess, so
    this process keeps its single-device view)."""
    code = textwrap.dedent(f"""
        import numpy as np, jax
        from jax.sharding import PartitionSpec as P
        from repro.core.graph import paper_graph
        from repro.core.partition_book import build_blockrow_book
        from repro.gnn.sync import RingSync, build_ring_blocks
        from repro.launch.hlo import collective_bytes_from_hlo
        from repro.launch.mesh import make_mesh

        g = paper_graph("{GRAPH}", scale={scale}, seed=0)
        k, d = {k}, {d}
        book = build_blockrow_book(g, k)
        feats = np.zeros((g.num_vertices, d), np.float32)
        blocks = build_ring_blocks(book, feats,
                                   np.zeros(g.num_vertices, np.int32),
                                   np.zeros(g.num_vertices, bool))
        mesh = make_mesh((k,), ("parts",))

        def per_device(blocks_local):
            blk = jax.tree.map(lambda a: a[0], blocks_local)
            sync = RingSync(axis="parts", k=k)
            h = sync.edge_aggregate(blk, blk.x,
                                    lambda s, dst, m: s * m[:, None])
            return h[None]

        fn = jax.shard_map(per_device, mesh=mesh, in_specs=(P("parts"),),
                           out_specs=P("parts"), check_vma=False)
        hlo = jax.jit(fn).lower(blocks).compile().as_text()
        coll = collective_bytes_from_hlo(hlo)
        print(coll["count_per_kind"].get("collective-permute", 0),
              coll["bytes_per_kind"].get("collective-permute", 0))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env={"XLA_FLAGS": f"--xla_force_host_platform_device_count={k}",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    count, per_dev = proc.stdout.strip().splitlines()[-1].split()
    return int(count), int(per_dev)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")  # grid set by env/common
    ap.add_argument("--out-json", default="")
    ap.add_argument("--out-csv", default="")
    ap.add_argument("--skip-hlo", action="store_true",
                    help="skip the subprocess HLO measurement (fast local "
                         "iteration; the analytic bytes rows still emit)")
    args = ap.parse_args(argv if argv is not None else [])

    from repro.gnn.sync import sync_bytes_per_round

    c = cache()
    g = c.graph(GRAPH, RING_SCALE, 0)
    spec = GNNSpec(model="sage", feature_dim=32, hidden_dim=32,
                   num_classes=8, num_layers=2)
    d = spec.hidden_dim
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 32)).astype(np.float32)
    labels = rng.integers(0, 8, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3

    rows, csv_lines = [], []

    def emit2(name, seconds, derived):
        emit(name, seconds, derived)
        csv_lines.append(f"{name},{seconds * 1e6:.1f},{derived}")

    claims_ok = True
    for k in KS:
        ring_rec = c.blockrow_partition(g, k)
        halo_rec = c.edge_partition(g, HALO_METHOD, k, 0)
        recs = {"ring": ring_rec, "halo": halo_rec, "dense": halo_rec}
        per_round = {
            "ring": sync_bytes_per_round(ring_rec.book, d, "ring"),
            "halo": sync_bytes_per_round(halo_rec.book, d, "halo"),
            "dense": sync_bytes_per_round(halo_rec.book, d, "dense"),
        }
        for mode, rec in recs.items():
            assignment = None if mode == "ring" else rec.assignment
            tr = FullBatchTrainer.build(
                g, assignment, k, spec, feats, labels, train,
                sync_mode=mode, mode="sim", seed=0)
            step_s = _time_steps(tr.train_step)
            est = cost_model.fullbatch_epoch(tr.book, spec)
            emit2(f"fig_ring.step.{GRAPH}.k{k}.{mode}", step_s,
                  f"round_bytes={per_round[mode]};"
                  f"partition_time={rec.partition_time:.4f};"
                  f"model_epoch_ms={est.epoch_time * 1e3:.2f}")
            row = fullbatch_result_row(
                GRAPH, rec.method, k, spec, metrics=rec.metrics,
                partition_time=rec.partition_time, est=est,
                sync_mode=mode)
            row["round_bytes"] = per_round[mode]
            row["measured_step_s"] = step_s
            rows.append(row)

        ring_below_dense = per_round["ring"] < per_round["dense"]
        claims_ok &= ring_below_dense
        if not args.skip_hlo:
            count, per_dev = _ring_hlo_bytes(k, d, RING_SCALE)
            match = (count == k - 1 and per_dev * k == per_round["ring"])
            claims_ok &= match
            emit2(f"fig_ring.hlo.{GRAPH}.k{k}", 0.0,
                  f"permutes={count};hlo_cluster_bytes={per_dev * k};"
                  f"analytic={per_round['ring']};match={match}")
            rows[-3]["hlo_round_bytes"] = per_dev * k  # the ring row
        emit2(f"fig_ring.bytes.{GRAPH}.k{k}", 0.0,
              f"ring={per_round['ring']};halo={per_round['halo']};"
              f"dense={per_round['dense']};"
              f"ring_below_dense={ring_below_dense}")

    emit2("fig_ring.claims", 0.0, f"all_pinned={claims_ok}")
    if args.out_json:
        write_rows(rows, args.out_json)
    if args.out_csv:
        with open(args.out_csv, "w") as f:
            f.write("name,us_per_call,derived\n")
            f.write("\n".join(csv_lines) + "\n")
    if not claims_ok:
        raise SystemExit("fig_ring: analytic/HLO byte pin failed")


if __name__ == "__main__":
    main(sys.argv[1:])
