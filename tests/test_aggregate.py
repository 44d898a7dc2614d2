"""The tentpole's correctness gate: the tiled/pallas aggregation backends
must match the scatter oracle — values AND gradients — standalone, under
vmap, and end-to-end through both trainers. (The shard_map leg lives in
test_dist_lowering.py, which needs forced host devices.)"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.edge_partition import partition_edges
from repro.core.vertex_partition import partition_vertices
from repro.gnn.fullbatch import FullBatchTrainer
from repro.gnn.minibatch import MiniBatchTrainer
from repro.gnn.models import GNNSpec
from repro.kernels import ops, ref


def _layout(dst, num_rows, **kw):
    """The `aggregate` keywords of a `prepare_tiled_edges` layout."""
    order, ldst, btile, _ = ops.prepare_tiled_edges(dst, num_rows, **kw)
    return {"edge_order": jnp.asarray(order), "local_dst": jnp.asarray(ldst),
            "block_tile": jnp.asarray(btile)}


@pytest.mark.parametrize("backend", ["tiled", "pallas"])
@pytest.mark.parametrize("e,v,f", [(700, 300, 16), (257, 256, 128), (64, 1000, 8)])
def test_aggregate_matches_scatter(e, v, f, backend):
    rng = np.random.default_rng(e + v)
    dst = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    msgs = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    lay = _layout(np.asarray(dst), v)
    expect = ops.aggregate(msgs, dst, v, backend="scatter")
    np.testing.assert_allclose(
        np.asarray(expect),
        np.asarray(ref.segment_sum_ref(msgs, dst, v)), rtol=1e-6, atol=1e-6)
    out = ops.aggregate(msgs, dst, v, backend=backend, **lay)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["tiled", "pallas"])
@pytest.mark.parametrize("e,v,f", [(700, 300, 16), (257, 256, 4), (64, 1000, 8)])
def test_aggregate_max_matches_scatter(e, v, f, backend):
    """reduce="max" through the tiled segment-reduce == the `at[].max`
    scatter oracle (rows with no edges are -inf under both)."""
    rng = np.random.default_rng(e + v)
    dst = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    msgs = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    lay = _layout(np.asarray(dst), v)
    expect = ops.aggregate(msgs, dst, v, backend="scatter", reduce="max")
    np.testing.assert_allclose(
        np.asarray(expect),
        np.asarray(ref.segment_max_ref(msgs, dst, v)), rtol=1e-6, atol=1e-6)
    out = ops.aggregate(msgs, dst, v, backend=backend, reduce="max", **lay)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["tiled", "pallas"])
def test_aggregate_max_grads_match_scatter(backend):
    """The masked-argmax-gather vjp of the standalone segment-max == the
    scatter-max autodiff (every row covered, continuous data -> no ties,
    so the max is differentiable)."""
    rng = np.random.default_rng(0)
    e, v, f = 500, 200, 16
    dst = np.concatenate([np.arange(v), rng.integers(0, v, e - v)])
    dst = jnp.asarray(dst.astype(np.int32))
    msgs = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    lay = _layout(np.asarray(dst), v)

    def loss(m, bk, **kw):
        return (ops.aggregate(m, dst, v, backend=bk, reduce="max",
                              **kw) ** 2).sum()

    g_ref = jax.grad(loss)(msgs, "scatter")
    g = jax.grad(loss)(msgs, backend, **lay)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["tiled", "pallas"])
def test_aggregate_max_tie_grads_split_like_scatter(backend):
    """On TIED maxima the vjp must follow the scatter oracle's even-split
    subgradient convention (regression: the tiled vjp used to hand every
    tied edge the full cotangent, doubling the gradient)."""
    dst = jnp.asarray(np.array([0, 0, 0, 1], np.int32))
    msgs = jnp.asarray(np.array(
        [[2.0], [2.0], [1.0], [5.0]], np.float32))  # edges 0,1 tie on row 0
    lay = _layout(np.asarray(dst), 2)

    def loss(m, bk, **kw):
        return ops.aggregate(m, dst, 2, backend=bk, reduce="max", **kw).sum()

    g_ref = jax.grad(loss)(msgs, "scatter")
    g = jax.grad(loss)(msgs, backend, **lay)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g),
                               [[0.5], [0.5], [0.0], [1.0]])


def test_aggregate_max_grad_ignores_dropped_tied_edge():
    """Regression: a `valid`-dropped edge whose message ties the surviving
    row max is NOT part of the computed max — it must get zero cotangent
    and must not deflate the survivors' tie split (the bwd used to compute
    the argmax mask over all edges but count ties over the layout only,
    leaking non-conservative gradient mass)."""
    dst = np.array([0, 0, 1], np.int32)
    msgs = jnp.asarray(np.array([[2.0], [2.0], [5.0]], np.float32))
    lay = _layout(dst, 2, valid=np.array([True, False, True]))

    def loss(m):
        return ops.aggregate(
            m, jnp.asarray(dst), 2, backend="tiled", reduce="max",
            **lay).sum()

    g = jax.grad(loss)(msgs)
    # edge 1 was dropped: the surviving argmax of row 0 is edge 0 alone
    np.testing.assert_allclose(np.asarray(g), [[1.0], [0.0], [1.0]])


def test_aggregate_max_under_vmap():
    rng = np.random.default_rng(1)
    k, e, v, f = 3, 400, 150, 8
    dst = rng.integers(0, v, (k, e)).astype(np.int32)
    msgs = rng.normal(size=(k, e, f)).astype(np.float32)
    n_blocks = max(ops.tiled_need_blocks(dst[p], v) for p in range(k))
    layouts = [ops.prepare_tiled_edges(dst[p], v, n_blocks=n_blocks)[:3]
               for p in range(k)]
    args = (jnp.asarray(msgs), jnp.asarray(dst),
            *(jnp.asarray(np.stack(a)) for a in zip(*layouts)))
    expect = jax.vmap(lambda m, d: ops.aggregate(
        m, d, v, backend="scatter", reduce="max"))(args[0], args[1])
    out = jax.vmap(lambda m, d, o, l, t: ops.aggregate(
        m, d, v, edge_order=o, local_dst=l, block_tile=t, backend="tiled",
        reduce="max"))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["tiled", "pallas"])
def test_aggregate_grads_match_scatter(backend):
    rng = np.random.default_rng(0)
    e, v, f = 500, 200, 16
    dst = jnp.asarray(rng.integers(0, v, e).astype(np.int32))
    msgs = jnp.asarray(rng.normal(size=(e, f)).astype(np.float32))
    lay = _layout(np.asarray(dst), v)

    def loss(m, bk, **kw):
        return (ops.aggregate(m, dst, v, backend=bk, **kw) ** 2).sum()

    g_ref = jax.grad(loss)(msgs, "scatter")
    g = jax.grad(loss)(msgs, backend, **lay)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


def test_aggregate_under_vmap():
    rng = np.random.default_rng(1)
    k, e, v, f = 3, 400, 150, 8
    dst = rng.integers(0, v, (k, e)).astype(np.int32)
    msgs = rng.normal(size=(k, e, f)).astype(np.float32)
    orders, ldsts, btiles = [], [], []
    # uniform static shape across the stacked layouts (forced per_tile)
    per_tile = max(ops.tiled_need_per_tile(dst[p], v) for p in range(k))
    for p in range(k):
        eo, ld, bt, _ = ops.prepare_tiled_edges(dst[p], v, per_tile=per_tile)
        orders.append(eo)
        ldsts.append(ld)
        btiles.append(bt)

    def agg(bk):
        def fn(m, d, o, l, t):
            return ops.aggregate(m, d, v, edge_order=o, local_dst=l,
                                 block_tile=t, backend=bk)
        return jax.vmap(fn)

    args = (jnp.asarray(msgs), jnp.asarray(dst),
            jnp.asarray(np.stack(orders)), jnp.asarray(np.stack(ldsts)),
            jnp.asarray(np.stack(btiles)))
    expect = jax.vmap(lambda m, d: ops.aggregate(m, d, v, backend="scatter"))(
        args[0], args[1])
    np.testing.assert_allclose(np.asarray(agg("tiled")(*args)),
                               np.asarray(expect), rtol=1e-5, atol=1e-5)

    # gradients under vmap
    def loss(bk):
        def fn(m, d, o, l, t):
            return (ops.aggregate(m, d, v, edge_order=o, local_dst=l,
                                  block_tile=t, backend=bk) ** 2).sum()
        return jax.vmap(jax.grad(fn))
    g_ref = jax.vmap(jax.grad(
        lambda m, d: (ops.aggregate(m, d, v, backend="scatter") ** 2).sum()
    ))(args[0], args[1])
    np.testing.assert_allclose(np.asarray(loss("tiled")(*args)),
                               np.asarray(g_ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the ragged tiled layout
# ---------------------------------------------------------------------------


def _skewed_dst(rng, v=1280):
    """Degree skew across row tiles of 256: a power-law tile 0, an empty
    tile 1, and light tiles after it."""
    heavy = (rng.pareto(1.2, 3000) * 20).astype(np.int64) % 256
    light = rng.integers(512, v, 400)
    return rng.permutation(np.concatenate([heavy, light])).astype(np.int32)


@pytest.mark.parametrize("case", ["uniform", "skewed", "valid", "tiny"])
def test_ragged_layout_bounds_rows_and_orders_blocks(case):
    """Each tile is padded to its own count: at most messages + n_tiles *
    block_e rows, every tile named by at least one block, blocks in tile
    order, and each edge placed once in its own tile's blocks."""
    rng = np.random.default_rng(7)
    valid = None
    if case == "uniform":
        v, dst = 1000, rng.integers(0, 1000, 5000).astype(np.int32)
    elif case == "skewed":
        v, dst = 1280, _skewed_dst(rng)
    elif case == "valid":
        v, dst = 1280, _skewed_dst(rng)
        valid = rng.random(dst.shape[0]) < 0.6
    else:
        v, dst = 7, rng.integers(0, 7, 40).astype(np.int32)
    order, ldst, btile, rows_p = ops.prepare_tiled_edges(dst, v, valid=valid)
    block_e = ops.DEFAULT_BLOCK_E
    n_tiles = rows_p // ops.DEFAULT_TILE_V
    kept = np.arange(dst.shape[0]) if valid is None else np.flatnonzero(valid)
    assert order.shape[0] == btile.shape[0] * block_e
    assert order.shape[0] <= kept.size + n_tiles * block_e
    assert btile.shape[0] == ops.tiled_need_blocks(dst, v, valid=valid)
    assert (np.diff(btile) >= 0).all()
    assert np.array_equal(np.unique(btile), np.arange(n_tiles))
    real = order < dst.shape[0]
    assert sorted(order[real]) == sorted(kept)
    rows_of = np.repeat(btile, block_e) * ops.DEFAULT_TILE_V + ldst
    np.testing.assert_array_equal(rows_of[real], dst[order[real]])
    assert (ldst[~real] == ops.DEFAULT_TILE_V).all()


def _layout_loop(dst, num_rows, blocks, valid=None):
    """Loop reference of the layout pass: tile by tile, each tile's edges
    in input order, then pad to its `blocks[t]` blocks."""
    tile_v, block_e = ops.DEFAULT_TILE_V, ops.DEFAULT_BLOCK_E
    keep = np.ones(dst.shape[0], bool) if valid is None else valid
    order, ldst, btile = [], [], []
    for t, nb in enumerate(blocks):
        mine = [i for i in range(dst.shape[0])
                if keep[i] and dst[i] // tile_v == t]
        pad = nb * block_e - len(mine)
        order += mine + [dst.shape[0]] * pad
        ldst += [int(dst[i]) - t * tile_v for i in mine] + [tile_v] * pad
        btile += [t] * nb
    return np.array(order), np.array(ldst), np.array(btile)


@pytest.mark.parametrize("forced", [False, True])
def test_layout_pass_matches_loop_reference(forced):
    """The vectorised layout pass places every edge where the tile-by-tile
    loop does, ragged and with a forced uniform `per_tile`."""
    rng = np.random.default_rng(10)
    v, dst = 1280, _skewed_dst(rng)
    valid = rng.random(dst.shape[0]) < 0.8
    need = np.maximum(-(-np.bincount(dst[valid] // ops.DEFAULT_TILE_V,
                                     minlength=5) // ops.DEFAULT_BLOCK_E), 1)
    kw = {}
    if forced:
        kw["per_tile"] = int(need.max()) * ops.DEFAULT_BLOCK_E
        need = np.full(5, need.max())
    got = ops.prepare_tiled_edges(dst, v, valid=valid, **kw)[:3]
    for a, b in zip(got, _layout_loop(dst, v, need, valid)):
        np.testing.assert_array_equal(a, b)


def test_forced_per_tile_layout_gives_trivial_map():
    """`per_tile` keeps its meaning, a uniform count: block b belongs to
    tile b // blocks_per_tile, and a forced total appends pad blocks on
    the last tile that reach no row."""
    rng = np.random.default_rng(8)
    v, dst = 1280, _skewed_dst(rng)
    per_tile = ops.tiled_need_per_tile(dst, v)
    order, ldst, btile, rows_p = ops.prepare_tiled_edges(
        dst, v, per_tile=per_tile)
    bpt = per_tile // ops.DEFAULT_BLOCK_E
    np.testing.assert_array_equal(btile, np.arange(btile.shape[0]) // bpt)
    assert btile.shape[0] == rows_p // ops.DEFAULT_TILE_V * bpt
    need = ops.tiled_need_blocks(dst, v)
    order2, ldst2, btile2, _ = ops.prepare_tiled_edges(
        dst, v, n_blocks=need + 3)
    n_tiles = rows_p // ops.DEFAULT_TILE_V
    assert btile2[need:].tolist() == [n_tiles - 1] * 3
    tail = need * ops.DEFAULT_BLOCK_E
    assert (ldst2[tail:] == ops.DEFAULT_TILE_V).all()
    assert (order2[tail:] == dst.shape[0]).all()
    with pytest.raises(AssertionError):
        ops.prepare_tiled_edges(dst, v, n_blocks=need - 1)


def test_stacked_edge_book_shares_one_block_count(or_graph):
    """k = 2: each partition's layout is its own ragged layout padded with
    trailing blocks to the larger need, so the stack has one shape."""
    from repro.core.partition_book import build_edge_book

    asg = partition_edges(or_graph, 2, "hdrf", seed=1)
    book = build_edge_book(or_graph, asg, 2, tiled_layout=True)
    n_blocks = book.agg_btile.shape[1]
    assert book.agg_order.shape == (2, n_blocks * ops.DEFAULT_BLOCK_E)
    assert book.agg_ldst.shape == book.agg_order.shape
    dst2 = np.concatenate([book.edst, book.esrc], axis=1)
    valid2 = np.concatenate([book.emask, book.emask], axis=1)
    needs = [ops.tiled_need_blocks(dst2[p], book.v_max + 1, valid=valid2[p])
             for p in range(2)]
    assert n_blocks == max(needs)
    for p in range(2):
        order, ldst, btile, _ = ops.prepare_tiled_edges(
            dst2[p], book.v_max + 1, n_blocks=n_blocks, valid=valid2[p])
        np.testing.assert_array_equal(book.agg_order[p], order)
        np.testing.assert_array_equal(book.agg_ldst[p], ldst)
        np.testing.assert_array_equal(book.agg_btile[p], btile)


def test_pad_ratio_gauge_recorded_under_book_build(or_graph):
    """`tiled.pad_ratio` (layout rows over real messages) is a gauge taken
    inside the `book.build` span, and only when the layout is built."""
    from repro.core.partition_book import build_edge_book
    from repro.obs.trace import tracing

    asg = partition_edges(or_graph, 2, "hdrf", seed=1)
    with tracing() as tr:
        book = build_edge_book(or_graph, asg, 2, tiled_layout=True)
        build_edge_book(or_graph, asg, 2)
    (gauge,) = tr.counters("tiled.pad_ratio")
    span = tr.spans("book.build")[0]
    assert span.t0 <= gauge.t <= span.t1
    messages = 2 * int(book.emask.sum())
    assert gauge.value == pytest.approx(book.agg_order.size / messages)
    assert gauge.value >= 1.0


def test_pad_ratio_or_full_scale():
    """The benchmark's graph, OR at scale 1.0 (k = 1): the ragged layout
    holds 1.009x the 2,684,556 real messages, where the uniform count of
    the busiest tile held 1.533x."""
    from repro.core.graph import paper_graph
    from repro.core.partition_book import build_edge_book
    from repro.obs.trace import tracing

    g = paper_graph("OR", scale=1.0, seed=0)
    with tracing() as tr:
        book = build_edge_book(g, np.zeros(g.num_edges, np.int32), 1,
                               tiled_layout=True)
    (gauge,) = tr.counters("tiled.pad_ratio")
    assert 2 * g.num_edges == 2_684_556
    assert book.agg_order.shape == (1, 2_709_504)
    assert gauge.value == pytest.approx(2_709_504 / 2_684_556)
    dst2 = np.concatenate([book.edst[0], book.esrc[0]])
    n_tiles = book.agg_btile.max() + 1
    uniform = ops.tiled_need_per_tile(dst2, book.v_max + 1) * n_tiles
    assert uniform == 4_114_432


@pytest.mark.parametrize("reduce", ["sum", "max"])
def test_aggregate_grads_match_scatter_on_ragged_layout(reduce):
    """The kernel (interpreted) on a skewed ragged layout with pad blocks:
    values and gradients match the scatter oracle for both combiners."""
    rng = np.random.default_rng(9)
    v, f = 1280, 8
    dst = _skewed_dst(rng)
    if reduce == "max":  # every row covered, so the max is differentiable
        dst = np.concatenate([dst, np.arange(v, dtype=np.int32)])
    msgs = jnp.asarray(rng.normal(size=(dst.shape[0], f)).astype(np.float32))
    lay = _layout(dst, v, n_blocks=ops.tiled_need_blocks(dst, v) + 2)
    jdst = jnp.asarray(dst)

    def loss(m, bk, **kw):
        return (ops.aggregate(m, jdst, v, backend=bk, reduce=reduce,
                              **kw) ** 2).sum()

    np.testing.assert_allclose(
        np.asarray(ops.aggregate(msgs, jdst, v, backend="pallas",
                                 reduce=reduce, interpret=True, **lay)),
        np.asarray(ops.aggregate(msgs, jdst, v, reduce=reduce)),
        rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(loss)(msgs, "scatter")
    g = jax.grad(loss)(msgs, "pallas", interpret=True, **lay)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# end-to-end: trainers with backend="tiled" == the scatter oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", ["sage", "gcn"])
@pytest.mark.parametrize("k", [1, 4])
def test_fullbatch_tiled_matches_scatter(or_graph, node_data, model, k):
    feats, labels, train = node_data
    spec = GNNSpec(model=model, feature_dim=16, hidden_dim=8, num_classes=5)
    asg = (np.zeros(or_graph.num_edges, np.int32) if k == 1
           else partition_edges(or_graph, k, "hdrf", seed=1))
    trainers = {}
    for backend in ("scatter", "tiled"):
        tr = FullBatchTrainer.build(
            or_graph, asg, k, dataclasses.replace(spec, agg_backend=backend),
            feats, labels, train, seed=7)
        losses = [tr.train_step() for _ in range(3)]
        trainers[backend] = (tr, losses)
    # training trajectories (loss after adam steps => gradients) must agree
    np.testing.assert_allclose(trainers["tiled"][1], trainers["scatter"][1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        trainers["tiled"][0].forward_logits_global(),
        trainers["scatter"][0].forward_logits_global(),
        rtol=1e-5, atol=1e-5)


def test_fullbatch_gat_tiled_matches_scatter(or_graph, node_data):
    """GAT routes ALL its edge reductions — softmax num/den sums AND the
    stabilisation segment-max — through aggregate; the trajectories (loss
    after an adam step => gradients too) must match the scatter oracle."""
    feats, labels, train = node_data
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5)
    asg = partition_edges(or_graph, 4, "hdrf", seed=1)
    logits, losses = {}, {}
    for backend in ("scatter", "tiled"):
        tr = FullBatchTrainer.build(
            or_graph, asg, 4, dataclasses.replace(spec, agg_backend=backend),
            feats, labels, train, seed=7)
        losses[backend] = [tr.train_step() for _ in range(2)]
        logits[backend] = tr.forward_logits_global()
    np.testing.assert_allclose(losses["tiled"], losses["scatter"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(logits["tiled"], logits["scatter"],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model", ["sage", "gat"])
def test_fullbatch_pallas_backend_smoke(or_graph, node_data, model):
    """backend="pallas" (interpreted on CPU) stays numerically exact
    end-to-end (gat also runs the max kernel); one small forward keeps
    this affordable in CI."""
    feats, labels, train = node_data
    spec = GNNSpec(model=model, feature_dim=16, hidden_dim=8, num_classes=5)
    asg = np.zeros(or_graph.num_edges, np.int32)
    out = {}
    for backend in ("scatter", "pallas"):
        tr = FullBatchTrainer.build(
            or_graph, asg, 1, dataclasses.replace(spec, agg_backend=backend),
            feats, labels, train, seed=7)
        out[backend] = tr.forward_logits_global()
    np.testing.assert_allclose(out["pallas"], out["scatter"],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# acceptance: no data-dependent scatter remains on the GAT hot path
# ---------------------------------------------------------------------------


def test_gat_forward_scatter_free_when_not_scatter(or_graph, node_data):
    """With agg_backend="pallas" the traced GAT forward contains NO
    data-dependent scatter-add/scatter-max — every O(E) edge reduction runs
    through the tiled kernel. Scope: the "tiled" backend off-TPU
    legitimately falls back to the jnp scatter oracle (on TPU it lowers to
    the same kernel as "pallas"), and with k>1 the replica sync still
    scatters into its bucket-sized halo buffers (O(replicas), the network
    path) — hence k=1/LocalSync here, which isolates the edge hot path.
    The walk + expectation live in `repro.analysis` (the gnn_lint
    no-scatter rule); this test pins the rule to these exact traces."""
    from repro.analysis import check_scatter
    from repro.gnn import models
    from repro.gnn.sync import LocalSync

    feats, labels, train = node_data
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   agg_backend="pallas")
    tr = FullBatchTrainer.build(
        or_graph, np.zeros(or_graph.num_edges, np.int32), 1, spec,
        feats, labels, train, seed=7)
    blk = jax.tree.map(lambda a: a[0], tr.blocks)
    jaxpr = jax.make_jaxpr(
        lambda params, x: models.forward(spec, params, x, blk, LocalSync())
    )(tr.params, blk.x)
    assert check_scatter([jaxpr], expect_free=True) is None

    # the scatter oracle, traced the same way, DOES contain both — the
    # anchor direction (expect_free=False) holds, so the assertion above
    # is meaningful
    spec_sc = dataclasses.replace(spec, agg_backend="scatter")
    jaxpr_sc = jax.make_jaxpr(
        lambda params, x: models.forward(spec_sc, params, x, blk, LocalSync())
    )(tr.params, blk.x)
    assert check_scatter([jaxpr_sc], expect_free=False) is None
    # and the walker misreports neither direction
    assert check_scatter([jaxpr_sc], expect_free=True) is not None


def test_minibatch_gat_forward_scatter_free_when_not_scatter(
        or_graph, node_data):
    """Same acceptance gate for the mini-batch GAT layer stack."""
    from repro.analysis import check_scatter
    from repro.gnn.minibatch import minibatch_loss

    feats, labels, train = node_data
    owner = partition_vertices(or_graph, 4, "metis", seed=0)
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   agg_backend="pallas")
    tr = MiniBatchTrainer.build(
        or_graph, owner, 4, spec, feats, labels, train,
        global_batch=64, seed=3)
    # pallas != scatter => the engine's preparer attaches the tiled layout
    pb = tr.engine.preparer.prepare()
    batch0 = jax.tree.map(lambda a: a[0], pb.stacked)
    sizes = tuple(tr._layer_sizes)
    jaxpr = jax.make_jaxpr(
        lambda params: minibatch_loss(spec, params, batch0, sizes, axis=None)
    )(tr.params)
    assert check_scatter([jaxpr], expect_free=True) is None


@pytest.mark.parametrize("model", ["sage", "gat"])
def test_minibatch_tiled_matches_scatter(or_graph, node_data, model):
    feats, labels, train = node_data
    owner = partition_vertices(or_graph, 4, "metis", seed=0)
    spec = GNNSpec(model=model, feature_dim=16, hidden_dim=8, num_classes=5)
    results = {}
    for backend in ("scatter", "tiled"):
        tr = MiniBatchTrainer.build(
            or_graph, owner, 4, dataclasses.replace(spec, agg_backend=backend),
            feats, labels, train, global_batch=64, seed=3)
        losses = [tr.train_step().loss for _ in range(3)]
        results[backend] = (losses, tr.params)
    np.testing.assert_allclose(results["tiled"][0], results["scatter"][0],
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(results["tiled"][1]),
                    jax.tree.leaves(results["scatter"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
