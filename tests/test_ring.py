"""1.5D ring-pipelined full-batch training (BlockRowBook + RingSync).

The tentpole invariant: ring == halo == the k=1 LocalSync oracle, to fp32
tolerance, for every model and aggregation backend — the block-rotation
schedule moves features instead of replica partials, but the mathematics is
the same global symmetrised aggregation.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.edge_partition import partition_edges
from repro.core.graph import paper_graph
from repro.core.partition_book import BlockRowBook, build_blockrow_book
from repro.gnn.fullbatch import FullBatchTrainer
from repro.gnn.models import GNNSpec
from repro.gnn.sync import (
    SYNC_MODES,
    RingBlock,
    build_ring_blocks,
    make_sync,
    ring_bytes_per_round,
    sync_bytes_per_round,
)
from repro.kernels.tiling import (
    DEFAULT_BLOCK_E,
    prepare_tiled_edges,
    tiled_shape,
)


# ---------------------------------------------------------------------------
# BlockRowBook invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 3, 4])
def test_blockrow_book_blocks_partition_vertices(or_graph, k):
    """The k blocks partition [0, V): every vertex appears exactly once, in
    its contiguous block, and pads are marked invalid."""
    book = build_blockrow_book(or_graph, k)
    V = or_graph.num_vertices
    assert book.vmask.sum() == V
    owned = book.vglobal[book.vmask]
    assert sorted(owned.tolist()) == list(range(V))
    # contiguity: block p owns exactly [p*Vb, min((p+1)*Vb, V))
    for p in range(k):
        lo, hi = p * book.v_block, min((p + 1) * book.v_block, V)
        got = np.sort(book.vglobal[p][book.vmask[p]])
        np.testing.assert_array_equal(got, np.arange(lo, hi))
    # the dummy row (index v_block) is never a real vertex
    assert not book.vmask[:, book.v_block].any()


@pytest.mark.parametrize("k", [1, 4])
def test_blockrow_chunk_edges_sum_to_E(or_graph, k):
    """Block-column chunk edge counts sum to 2E (both directions of every
    stored edge live in exactly one chunk) and every chunk holds only edges
    with dst in its block row and src in its stage's block."""
    book = build_blockrow_book(or_graph, k)
    assert int(book.chunk_emask.sum()) == 2 * or_graph.num_edges
    want = set(zip(
        np.concatenate([or_graph.src, or_graph.dst]).tolist(),
        np.concatenate([or_graph.dst, or_graph.src]).tolist(),
    ))
    got = set()
    for p in range(k):
        for s in range(k):
            m = book.chunk_emask[p, s]
            src_blk = (p + s) % k
            gsrc = book.chunk_esrc[p, s][m] + src_blk * book.v_block
            gdst = book.chunk_edst[p, s][m] + p * book.v_block
            # locality: dst in block p, src in block (p+s) mod k
            assert (gdst // book.v_block == p).all()
            assert (gsrc // book.v_block == src_blk).all()
            got.update(zip(gsrc.tolist(), gdst.tolist()))
    assert got == want
    # pads point at the dummy row
    pads = ~book.chunk_emask
    assert (book.chunk_esrc[pads] == book.v_block).all()
    assert (book.chunk_edst[pads] == book.v_block).all()


@pytest.mark.parametrize("k", [1, 4])
def test_blockrow_tiled_layouts_roundtrip(or_graph, k):
    """Per-chunk tiled layouts agree with a fresh `prepare_tiled_edges` pass
    over the same chunk (validation round-trip), with ONE shared block count
    so the stacked [k, k, ...] arrays have a static shape."""
    book = build_blockrow_book(or_graph, k, tiled_layout=True)
    n_rows = book.v_block + 1
    _, n_tiles = tiled_shape(n_rows)
    n_blocks = book.chunk_agg_btile.shape[-1]
    assert book.chunk_agg_order.shape[-1] == n_blocks * DEFAULT_BLOCK_E
    assert n_blocks >= n_tiles
    for p in range(k):
        for s in range(k):
            order, ldst, btile, rows_padded = prepare_tiled_edges(
                book.chunk_edst[p, s], n_rows, n_blocks=n_blocks,
                valid=book.chunk_emask[p, s])
            np.testing.assert_array_equal(book.chunk_agg_order[p, s], order)
            np.testing.assert_array_equal(book.chunk_agg_ldst[p, s], ldst)
            np.testing.assert_array_equal(book.chunk_agg_btile[p, s], btile)


def test_blockrow_partitioner_registered(or_graph):
    """"blockrow" is a plain edge partitioner too, so the 1.5D layout can be
    measured by the standard metrics and driven through halo/dense sync."""
    a = partition_edges(or_graph, 4, "blockrow")
    v_block = -(-or_graph.num_vertices // 4)
    np.testing.assert_array_equal(a, or_graph.dst // v_block)


# ---------------------------------------------------------------------------
# Ring == halo == k=1 oracle (sim mode; shard_map in test_dist_lowering.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
@pytest.mark.parametrize("backend", ["scatter", "tiled"])
def test_ring_equals_oracle_forward(or_graph, node_data, model, backend):
    feats, labels, train = node_data
    spec = GNNSpec(model=model, feature_dim=16, hidden_dim=8, num_classes=5,
                   num_layers=2, agg_backend=backend)
    ref = FullBatchTrainer.build(
        or_graph, np.zeros(or_graph.num_edges, np.int32), 1, spec,
        feats, labels, train, seed=7)
    ref_logits = ref.forward_logits_global()
    for k in (1, 4):
        tr = FullBatchTrainer.build(
            or_graph, None, k, spec, feats, labels, train,
            sync_mode="ring", mode="sim", seed=7)
        assert isinstance(tr.book, BlockRowBook)
        np.testing.assert_allclose(tr.forward_logits_global(), ref_logits,
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("model", ["sage", "gcn", "gat"])
def test_ring_equals_halo_training(or_graph, node_data, model):
    """Loss trajectories: ring == halo == k=1 oracle over 3 steps."""
    feats, labels, train = node_data
    spec = GNNSpec(model=model, feature_dim=16, hidden_dim=8, num_classes=5,
                   num_layers=2)
    ref = FullBatchTrainer.build(
        or_graph, np.zeros(or_graph.num_edges, np.int32), 1, spec,
        feats, labels, train, seed=7)
    halo = FullBatchTrainer.build(
        or_graph, partition_edges(or_graph, 4, "hdrf", seed=1), 4, spec,
        feats, labels, train, sync_mode="halo", mode="sim", seed=7)
    ring = FullBatchTrainer.build(
        or_graph, None, 4, spec, feats, labels, train,
        sync_mode="ring", mode="sim", seed=7)
    for step in range(3):
        l_ref = ref.train_step()
        l_halo = halo.train_step()
        l_ring = ring.train_step()
        assert abs(l_ref - l_ring) < 1e-4, (step, l_ref, l_ring)
        assert abs(l_halo - l_ring) < 1e-4, (step, l_halo, l_ring)


def test_ring_loss_decreases(or_graph, node_data):
    feats, labels, train = node_data
    spec = GNNSpec(model="sage", feature_dim=16, hidden_dim=16, num_classes=5,
                   num_layers=2)
    tr = FullBatchTrainer.build(
        or_graph, None, 4, spec, feats, labels, train,
        sync_mode="ring", mode="sim", seed=3, lr=5e-2)
    losses = [tr.train_step() for _ in range(8)]
    assert losses[-1] < losses[0]


def test_ring_tiled_equals_scatter_training(or_graph, node_data):
    """The tiled backend's ring gradients match the scatter oracle's."""
    feats, labels, train = node_data
    spec = GNNSpec(model="sage", feature_dim=16, hidden_dim=8, num_classes=5)
    outs = {}
    for backend in ("scatter", "tiled"):
        tr = FullBatchTrainer.build(
            or_graph, None, 4, dataclasses.replace(spec, agg_backend=backend),
            feats, labels, train, sync_mode="ring", mode="sim", seed=7)
        losses = [tr.train_step() for _ in range(2)]
        outs[backend] = (losses, tr.forward_logits_global())
    assert abs(outs["tiled"][0][-1] - outs["scatter"][0][-1]) < 1e-6
    np.testing.assert_allclose(outs["tiled"][1], outs["scatter"][1],
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# make_sync surface + analytic volume
# ---------------------------------------------------------------------------


def test_make_sync_unknown_mode_lists_strategies():
    with pytest.raises(ValueError) as exc:
        make_sync("gossip", None, 10, "parts")
    msg = str(exc.value)
    for mode in SYNC_MODES:
        assert mode in msg, (mode, msg)


def test_make_sync_ring_needs_ring_block(or_graph, node_data):
    """A halo Block cannot drive the ring (no chunk tables): clear TypeError
    instead of a silent attribute crash mid-trace."""
    from repro.core.partition_book import build_edge_book
    from repro.gnn.sync import build_blocks

    feats, labels, train = node_data
    book = build_edge_book(
        or_graph, np.zeros(or_graph.num_edges, np.int32), 1)
    blk = build_blocks(book, feats, labels, train)
    with pytest.raises(TypeError):
        make_sync("ring", blk, or_graph.num_vertices, "parts")


@pytest.mark.parametrize("k", [2, 4, 8])
def test_ring_bytes_formula_below_dense(or_graph, k):
    """ring = k·(k−1)·(Vb+1)·d·4 cluster-wide — strictly below DenseSync's
    2·k·(V+1)·d·4 at every k (the 1.5D regime's bandwidth argument)."""
    from repro.core import cost_model
    from repro.core.partition_book import build_edge_book

    d = 64
    book = build_blockrow_book(or_graph, k)
    ring = sync_bytes_per_round(book, d, "ring")
    assert ring == book.k * (book.k - 1) * (book.v_block + 1) * d * 4
    assert ring == ring_bytes_per_round(book, d)
    assert ring == cost_model.ring_bytes_per_round(book, d)
    ebook = build_edge_book(
        or_graph, partition_edges(or_graph, k, "blockrow"), k)
    dense = sync_bytes_per_round(ebook, d, "dense")
    assert ring < dense, (k, ring, dense)


def test_ring_cost_model_epoch(or_graph):
    """The overlap-aware ring estimate prices a BlockRowBook end-to-end and
    exposes only the non-overlapped transfer remainder as comm_time."""
    from repro.core import cost_model

    spec = GNNSpec(model="sage", feature_dim=64, hidden_dim=64, num_classes=16)
    book = build_blockrow_book(or_graph, 4)
    est = cost_model.fullbatch_epoch(book, spec)
    assert est.epoch_time > 0
    assert est.comm_bytes.shape == (4,)
    syncs = 2  # sage: 1 aggregate per layer, fwd+bwd
    dims = [dout for _, dout in spec.dims()]
    expect = 3 * (book.v_block + 1) * 4 * sum(dims) * syncs
    np.testing.assert_allclose(est.comm_bytes, expect)
    # exposed comm can never exceed the full (unoverlapped) transfer time
    assert (est.comm_time >= 0).all()


def test_ring_study_row(or_graph):
    """study.fullbatch_row(sync_mode="ring") emits a blockrow row with
    near-zero partition time — the tab3 amortization contender."""
    from repro.core import study

    spec = GNNSpec(model="sage", feature_dim=16, hidden_dim=16, num_classes=5)
    row = study.fullbatch_row("OR", "blockrow", 4, spec, scale=0.02,
                              cache=study.StudyCache(), sync_mode="ring")
    assert row["sync_mode"] == "ring"
    assert row["method"] == "blockrow"
    assert row["partition_time"] < 0.1
    assert row["epoch_time"] > 0


# ---------------------------------------------------------------------------
# RingBlock plumbing
# ---------------------------------------------------------------------------


def test_ring_blocks_layout(or_graph, node_data):
    feats, labels, train = node_data
    book = build_blockrow_book(or_graph, 4)
    blocks = build_ring_blocks(book, feats, labels, train)
    assert isinstance(blocks, RingBlock)
    assert blocks.x.shape == (4, book.v_block + 1, feats.shape[1])
    # features land on the owner's rows
    x = np.asarray(blocks.x)
    for p in range(4):
        vm = book.vmask[p]
        np.testing.assert_array_equal(x[p][vm], feats[book.vglobal[p][vm]])
        np.testing.assert_array_equal(x[p][~vm], 0.0)
    # masters == vmask (single-owner layout)
    np.testing.assert_array_equal(np.asarray(blocks.master), book.vmask)
