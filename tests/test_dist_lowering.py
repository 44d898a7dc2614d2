"""Distribution-layer tests that need >1 device: run in a subprocess with
placeholder host devices so the main test process keeps 1 device."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The LM distribution layer (repro.dist: step builders, sharding policies,
# analytic costs) is not part of every build of this repo; the GNN study
# stands alone without it. Gate rather than fail.
requires_dist = pytest.mark.skipif(
    importlib.util.find_spec("repro.dist") is None,
    reason="repro.dist (LM distribution layer) not present in this build",
)


def _run(code: str, devices: int = 8) -> str:
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
        env={"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
             # pin the backend: without it jax burns minutes probing for
             # TPU/GPU plugins before falling back to CPU
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@requires_dist
def test_small_mesh_lowering_all_kinds():
    """train/prefill/decode cells lower+compile on a small (2,4) mesh for a
    smoke config — the same machinery the 512-device dry-run uses."""
    out = _run("""
        import dataclasses, jax, json
        from repro.configs.base import smoke_config, SHAPES
        from repro.dist import steps as S
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        cfg = smoke_config("qwen3-4b")
        results = {}
        for name, seq, batch in [("train_4k", 128, 8), ("prefill_32k", 256, 8),
                                 ("decode_32k", 256, 8)]:
            shape = dataclasses.replace(SHAPES[name], seq_len=seq, global_batch=batch)
            cell = S.build_cell(cfg, shape, mesh)
            compiled = cell.lower(mesh).compile()
            results[name] = compiled.cost_analysis().get("flops", 0) > 0
        print(json.dumps(results))
    """)
    results = json.loads(out.strip().splitlines()[-1])
    assert all(results.values()), results


def test_multipod_mesh_axes():
    out = _run("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh(multi_pod=True)
        print(sorted(m.shape.items()))
    """, devices=512)
    assert "('data', 16)" in out and "('model', 16)" in out and "('pod', 2)" in out


def test_gnn_fullbatch_shard_map_multidevice():
    """The GNN full-batch trainer runs under REAL shard_map over 4 devices
    and matches the single-device oracle."""
    out = _run("""
        import numpy as np, jax
        from repro.core.graph import paper_graph
        from repro.core.edge_partition import partition_edges
        from repro.gnn.fullbatch import FullBatchTrainer
        from repro.gnn.models import GNNSpec
        from repro.launch.mesh import make_mesh

        g = paper_graph("OR", scale=0.01, seed=0)
        rng = np.random.default_rng(0)
        feats = rng.normal(size=(g.num_vertices, 8)).astype(np.float32)
        labels = rng.integers(0, 4, g.num_vertices).astype(np.int32)
        train = rng.random(g.num_vertices) < 0.3
        spec = GNNSpec(model="sage", feature_dim=8, hidden_dim=8, num_classes=4)

        ref = FullBatchTrainer.build(g, np.zeros(g.num_edges, np.int32), 1,
                                     spec, feats, labels, train, seed=7)
        a = partition_edges(g, 4, "hdrf", seed=1)
        mesh = make_mesh((4,), ("parts",))
        tr = FullBatchTrainer.build(g, a, 4, spec, feats, labels, train,
                                    sync_mode="halo", mode="shard_map",
                                    mesh=mesh, seed=7)
        err = np.abs(tr.forward_logits_global() - ref.forward_logits_global()).max()
        print("maxerr", err)
        assert err < 2e-4, err
    """, devices=4)
    assert "maxerr" in out


def test_gnn_fullbatch_tiled_backend_shard_map():
    """The tiled aggregation backend under REAL shard_map over 4 devices ==
    the scatter oracle (the tentpole's multi-device correctness gate)."""
    out = _run("""
        import dataclasses, numpy as np, jax
        from repro.core.graph import paper_graph
        from repro.core.edge_partition import partition_edges
        from repro.gnn.fullbatch import FullBatchTrainer
        from repro.gnn.models import GNNSpec
        from repro.launch.mesh import make_mesh

        g = paper_graph("OR", scale=0.01, seed=0)
        rng = np.random.default_rng(0)
        feats = rng.normal(size=(g.num_vertices, 8)).astype(np.float32)
        labels = rng.integers(0, 4, g.num_vertices).astype(np.int32)
        train = rng.random(g.num_vertices) < 0.3
        spec = GNNSpec(model="sage", feature_dim=8, hidden_dim=8, num_classes=4)

        a = partition_edges(g, 4, "hdrf", seed=1)
        mesh = make_mesh((4,), ("parts",))
        outs = {}
        for backend in ("scatter", "tiled"):
            tr = FullBatchTrainer.build(
                g, a, 4, dataclasses.replace(spec, agg_backend=backend),
                feats, labels, train, sync_mode="halo", mode="shard_map",
                mesh=mesh, seed=7)
            loss = tr.train_step()
            outs[backend] = (loss, tr.forward_logits_global())
        err = np.abs(outs["tiled"][1] - outs["scatter"][1]).max()
        dl = abs(outs["tiled"][0] - outs["scatter"][0])
        print("maxerr", err, "dloss", dl)
        assert err < 1e-5 and dl < 1e-6, (err, dl)
    """, devices=4)
    assert "maxerr" in out


def test_segment_max_tiled_under_shard_map():
    """aggregate(reduce="max") with the tiled backend under REAL shard_map
    over 4 devices == the scatter `at[].max` oracle (the segment-max leg of
    the tentpole's multi-device correctness gate)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops
        from repro.launch.mesh import make_mesh

        k, e, v, f = 4, 400, 300, 8
        rng = np.random.default_rng(0)
        # cover every row so no -inf (empty-row identity) enters the diff
        dst = np.stack([np.concatenate([rng.permutation(v),
                                        rng.integers(0, v, e - v)])
                        for _ in range(k)]).astype(np.int32)
        msgs = rng.normal(size=(k, e, f)).astype(np.float32)
        per_tile = max(ops.prepare_tiled_edges(dst[p], v)[0].shape[0]
                       for p in range(k)) // ops.tiled_shape(v)[1]
        lay = [ops.prepare_tiled_edges(dst[p], v, per_tile=per_tile)[:2]
               for p in range(k)]
        order = np.stack([o for o, _ in lay])
        ldst = np.stack([l for _, l in lay])
        mesh = make_mesh((k,), ("parts",))

        def per_device(m, d, o, l):
            out = ops.aggregate(m[0], d[0], v, edge_order=o[0],
                                local_dst=l[0], backend="tiled", reduce="max")
            return out[None]

        fn = jax.shard_map(per_device, mesh=mesh,
                           in_specs=(P("parts"),) * 4, out_specs=P("parts"),
                           check_vma=False)
        got = jax.jit(fn)(jnp.asarray(msgs), jnp.asarray(dst),
                          jnp.asarray(order), jnp.asarray(ldst))
        expect = jax.vmap(lambda m, d: ops.aggregate(
            m, d, v, backend="scatter", reduce="max"))(
            jnp.asarray(msgs), jnp.asarray(dst))
        err = np.abs(np.asarray(got) - np.asarray(expect)).max()
        print("maxerr", err)
        assert err < 1e-6, err
    """, devices=4)
    assert "maxerr" in out


def test_gnn_fullbatch_ring_shard_map_multidevice():
    """RingSync (1.5D ppermute rotation) under REAL shard_map over 4 devices
    matches the single-device oracle, forward and loss trajectory — the
    tentpole's multi-device correctness gate for the ring strategy."""
    out = _run("""
        import numpy as np, jax
        from repro.core.graph import paper_graph
        from repro.gnn.fullbatch import FullBatchTrainer
        from repro.gnn.models import GNNSpec
        from repro.launch.mesh import make_mesh

        g = paper_graph("OR", scale=0.01, seed=0)
        rng = np.random.default_rng(0)
        feats = rng.normal(size=(g.num_vertices, 8)).astype(np.float32)
        labels = rng.integers(0, 4, g.num_vertices).astype(np.int32)
        train = rng.random(g.num_vertices) < 0.3
        mesh = make_mesh((4,), ("parts",))
        for model in ("sage", "gat"):
            spec = GNNSpec(model=model, feature_dim=8, hidden_dim=8,
                           num_classes=4)
            ref = FullBatchTrainer.build(g, np.zeros(g.num_edges, np.int32),
                                         1, spec, feats, labels, train, seed=7)
            tr = FullBatchTrainer.build(g, None, 4, spec, feats, labels,
                                        train, sync_mode="ring",
                                        mode="shard_map", mesh=mesh, seed=7)
            err = np.abs(tr.forward_logits_global()
                         - ref.forward_logits_global()).max()
            assert err < 2e-4, (model, err)
            for step in range(2):
                dl = abs(ref.train_step() - tr.train_step())
                assert dl < 1e-4, (model, step, dl)
            print("model", model, "maxerr", err)
    """, devices=4)
    assert "maxerr" in out


def test_ring_sync_bytes_match_compiled_hlo():
    """`ring_bytes_per_round` (k·(k−1)·(Vb+1)·d·4 cluster-wide) pinned
    against the collective-permute bytes XLA actually emitted: one ring
    aggregate compiles to EXACTLY k−1 permutes of the [Vb+1, d] payload
    block per device (the last rotation is elided). Driven through the
    gnn_lint collective-budget rule over the analysis program grid — the
    exact byte equality is now the rule's budget prediction."""
    out = _run("""
        import numpy as np
        from repro.analysis import analyze_hlo, build_programs, run_rules
        from repro.core.graph import paper_graph
        from repro.core.partition_book import build_blockrow_book
        from repro.gnn.sync import ring_bytes_per_round

        k, d = 4, 8
        progs = [p for p in build_programs("smoke")
                 if p.name == "hlo/ring-fp32"]
        assert len(progs) == 1
        report = run_rules(progs, ["collective-budget"])
        assert report.exit_code == 0, [f.message for f in report.errors]
        assert not any("skipped" in f.message for f in report.findings)

        # the rule's budget IS the analytic pin, and the compiled HLO
        # matches it exactly
        res = analyze_hlo(progs[0].make())
        got = res["bytes_per_kind"]["collective-permute"]
        book = build_blockrow_book(paper_graph("OR", scale=0.01, seed=0), k)
        expect_cluster = ring_bytes_per_round(book, d)
        print("cp_count", res["count_per_kind"]["collective-permute"],
              "per_device", got, "cluster", expect_cluster)
        assert res["count_per_kind"]["collective-permute"] == k - 1
        assert got * k == expect_cluster, (got, k, expect_cluster)
    """, devices=4)
    assert "cp_count 3" in out


def test_ring_sync_int8_codec_shrinks_compiled_hlo():
    """With the int8 wire codec the compiled ring rotation moves s8 payload
    (+ one f32 scale per block): cluster permute bytes equal
    `sync_wire_bytes_per_round(..., codec="int8")` = k·(k−1)·((Vb+1)·d + 4)
    — a ~4x shrink vs the fp32 pin above. The payload and its scale may
    lower as separate permutes, so the op count lands in [k−1, 2(k−1)].
    Driven through the gnn_lint collective-budget rule."""
    out = _run("""
        import numpy as np
        from repro.analysis import analyze_hlo, build_programs, run_rules
        from repro.core.graph import paper_graph
        from repro.core.partition_book import build_blockrow_book
        from repro.gnn.sync import ring_bytes_per_round, \\
            sync_wire_bytes_per_round

        k, d = 4, 8
        progs = [p for p in build_programs("smoke")
                 if p.name == "hlo/ring-int8"]
        report = run_rules(progs, ["collective-budget"])
        assert report.exit_code == 0, [f.message for f in report.errors]
        assert not any("skipped" in f.message for f in report.findings)

        res = analyze_hlo(progs[0].make())
        got = res["bytes_per_kind"]["collective-permute"]
        count = res["count_per_kind"]["collective-permute"]
        book = build_blockrow_book(paper_graph("OR", scale=0.01, seed=0), k)
        expect_wire = sync_wire_bytes_per_round(book, d, "ring",
                                                codec="int8")
        fp32_cluster = ring_bytes_per_round(book, d)
        print("cp_count", count, "cluster", got * k,
              "wire", expect_wire, "fp32", fp32_cluster)
        assert got * k == expect_wire, (got, k, expect_wire)
        assert k - 1 <= count <= 2 * (k - 1), count
        # the quarter-width claim, with slack for the per-block f32 scale
        assert got * k < 0.3 * fp32_cluster, (got * k, fp32_cluster)
    """, devices=4)
    assert "cp_count" in out


def test_halo_sync_bytes_match_compiled_hlo():
    """`sync_bytes_per_round` (2*k^2*B*d*4 cluster-wide for halo) pinned
    against the all-to-all bytes XLA actually emitted: the compiled
    per-device program moves 2*k*B*d*4 bytes per reduce+broadcast pair.
    Driven through the gnn_lint collective-budget rule."""
    out = _run("""
        import numpy as np
        from repro.analysis import analyze_hlo, build_programs, run_rules
        from repro.core.edge_partition import partition_edges
        from repro.core.graph import paper_graph
        from repro.core.partition_book import build_edge_book
        from repro.gnn.sync import sync_bytes_per_round

        k, d = 4, 8
        progs = [p for p in build_programs("smoke")
                 if p.name == "hlo/halo-fp32"]
        report = run_rules(progs, ["collective-budget"])
        assert report.exit_code == 0, [f.message for f in report.errors]
        assert not any("skipped" in f.message for f in report.findings)

        res = analyze_hlo(progs[0].make())
        got = res["bytes_per_kind"]["all-to-all"]
        g = paper_graph("OR", scale=0.01, seed=0)
        book = build_edge_book(g, partition_edges(g, k, "hdrf", seed=1), k)
        expect_cluster = sync_bytes_per_round(book, d, "halo")
        print("a2a_count", res["count_per_kind"]["all-to-all"],
              "per_device", got, "cluster", expect_cluster)
        assert res["count_per_kind"]["all-to-all"] == 2
        assert got * k == expect_cluster, (got, k, expect_cluster)
    """, devices=4)
    assert "a2a_count 2" in out


@requires_dist  # launch.dryrun imports the repro.dist cost/step builders
def test_dryrun_collective_parser():
    from repro.launch.dryrun import collective_bytes_from_hlo

    hlo = """
      %ar = f32[1024,8]{1,0} all-reduce(f32[1024,8]{1,0} %x), replica_groups={}
      %ag = (bf16[64]{0}, bf16[32]{0}) all-gather-start(bf16[32]{0} %y)
      %aa = f32[16,4]{1,0} all-to-all(f32[16,4]{1,0} %z)
      %c = f32[2] copy(f32[2] %w)
    """
    res = collective_bytes_from_hlo(hlo)
    assert res["count_per_kind"]["all-reduce"] == 1
    assert res["bytes_per_kind"]["all-reduce"] == 1024 * 8 * 4
    assert res["count_per_kind"]["all-gather"] == 1
    # the -start tuple echoes its bf16[32] operand; only the gathered
    # bf16[64] result is payload under the hardened parser
    assert res["bytes_per_kind"]["all-gather"] == 64 * 2
    assert res["count_per_kind"]["all-to-all"] == 1
    assert "copy" not in res["count_per_kind"]
