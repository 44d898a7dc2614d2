"""The serving subsystem's acceptance gate.

  * layer-wise inference == full-batch forward (fp32 tolerance) under the
    scatter, tiled AND pallas aggregation backends — the embedding stores
    hold exactly what training's forward would compute
  * embedding stores are lossless row stores with conserved accounting
  * the micro-batcher pads every request mix to ONE static shape (the
    serve step compiles once)
  * the online answer (store fetch + final-layer recompute) equals the
    offline layer-wise logits exactly when the fanout covers the full
    neighborhood (SAGE; sampled fanouts are approximate by design)
  * the cost model is monotone: more embedding misses => strictly larger
    modeled service time; a better partitioner => fewer miss bytes =>
    lower modeled latency, end to end
"""

import dataclasses

import numpy as np
import pytest

from repro.core import cost_model
from repro.core.graph import generate_graph
from repro.core.partition_book import build_vertex_book
from repro.core.vertex_partition import partition_vertices
from repro.gnn.fullbatch import FullBatchTrainer
from repro.gnn.inference import (
    LayerwiseInference,
    build_embedding_stores,
    edge_assignment_from_vertex,
    vertex_book_for,
)
from repro.gnn.models import GNNSpec, init_params
from repro.serve import MicroBatcher, build_serving, run_serving_sim
from repro.serve.batcher import plan_dispatch


@pytest.fixture(scope="module")
def tiny_graph():
    """Small undirected social graph (self-loop-free by construction)."""
    return generate_graph("social", 150, 900, seed=3)


@pytest.fixture(scope="module")
def node_setup(tiny_graph):
    g = tiny_graph
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 12)).astype(np.float32)
    return g, feats


# ---------------------------------------------------------------------------
# layer-wise inference engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scatter", "tiled", "pallas"])
@pytest.mark.parametrize("model", ["sage", "gat"])
def test_layerwise_matches_fullbatch_forward(node_setup, backend, model):
    """Acceptance: the embedding-store inference equals the full-batch
    forward to fp32 tolerance under all three aggregation backends."""
    g, feats = node_setup
    rng = np.random.default_rng(1)
    labels = rng.integers(0, 5, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    spec = GNNSpec(model=model, feature_dim=12, hidden_dim=8, num_classes=5,
                   num_layers=2, agg_backend=backend)
    from repro.core.edge_partition import partition_edges
    a = partition_edges(g, 4, "hep100", seed=0)
    tr = FullBatchTrainer.build(g, a, 4, spec, feats, labels, train, seed=0)
    eng = LayerwiseInference.build(g, a, 4, spec, tr.params, feats)
    embs = eng.run()
    assert len(embs) == spec.num_layers
    assert embs[0].shape == (g.num_vertices, spec.hidden_dim)
    assert embs[-1].shape == (g.num_vertices, spec.num_classes)
    np.testing.assert_allclose(
        embs[-1], tr.forward_logits_global(), rtol=1e-5, atol=1e-5)


def test_layerwise_k1_is_single_machine(node_setup):
    g, feats = node_setup
    spec = GNNSpec(model="gcn", feature_dim=12, hidden_dim=8, num_classes=5,
                   num_layers=3)
    params = init_params(spec, seed=2)
    single = LayerwiseInference.build(
        g, np.zeros(g.num_edges, np.int64), 1, spec, params, feats)
    multi = LayerwiseInference.build(
        g, edge_assignment_from_vertex(
            g, partition_vertices(g, 3, "metis", seed=0)), 3, spec, params,
        feats)
    for a, b in zip(single.run(), multi.run()):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_embedding_stores_are_lossless(node_setup):
    g, feats = node_setup
    spec = GNNSpec(model="sage", feature_dim=12, hidden_dim=8, num_classes=5)
    params = init_params(spec, seed=0)
    owner = partition_vertices(g, 3, "metis", seed=0)
    eng = LayerwiseInference.build(
        g, edge_assignment_from_vertex(g, owner), 3, spec, params, feats)
    embs = eng.run()
    vbook = build_vertex_book(g, owner, 3)
    stores = build_embedding_stores(g, vbook, embs, policy="degree",
                                    budget=20, seed=0)
    rng = np.random.default_rng(5)
    for li, store in enumerate(stores):
        assert store.row_dim == embs[li].shape[1]
        for w in range(3):
            ids = rng.integers(0, g.num_vertices, 64)
            rows, st = store.gather(w, ids)
            np.testing.assert_array_equal(rows, embs[li][ids])
            assert st.num_local + st.num_cache_hit + st.num_remote_miss == 64
            assert st.miss_bytes == st.num_remote_miss * 4 * store.row_dim
    # one shared cache selection across layers
    for w in range(3):
        np.testing.assert_array_equal(stores[0].cached_ids(w),
                                      stores[1].cached_ids(w))


def test_master_assignment_roundtrip(node_setup):
    g, feats = node_setup
    from repro.core.edge_partition import partition_edges
    from repro.core.partition_book import build_edge_book
    book = build_edge_book(g, partition_edges(g, 4, "hdrf", seed=0), 4)
    owner = book.master_assignment()
    assert owner.shape == (g.num_vertices,)
    assert owner.min() >= 0 and owner.max() < 4
    vb = vertex_book_for(g, book)
    assert vb.k == 4
    np.testing.assert_array_equal(vb.owner, owner)


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------


def test_batcher_static_shapes(node_setup):
    """Padding invariant: every request mix produces identical shapes."""
    g, _ = node_setup
    owner = partition_vertices(g, 2, "metis", seed=0)
    b = MicroBatcher.build(g, fanouts=(5, 5), max_batch=16, owner=owner,
                           worker=0, tiled_layout=True, seed=0)
    hub = int(np.argmax(g.degrees()))
    mixes = [
        np.array([0]),                                  # single request
        np.arange(16),                                  # full batch
        np.full(16, hub),                               # duplicates of a hub
        np.array([hub] * 3 + [0, 1]),                   # mixed
    ]
    shapes = set()
    for ids in mixes:
        mfg = b.build_mfg(ids)
        sig = (mfg.input_ids.shape, tuple(
            (l.esrc.shape, l.edst.shape, l.emask.shape,
             l.sampled_deg.shape, l.agg_order.shape, l.agg_ldst.shape,
             l.agg_btile.shape)
            for l in mfg.layers), mfg.seed_labels.shape)
        shapes.add(sig)
        assert int(mfg.seed_mask.sum()) == ids.shape[0]
    assert len(shapes) == 1
    with pytest.raises(ValueError):
        b.build_mfg(np.arange(17))
    with pytest.raises(ValueError):
        b.build_mfg(np.zeros(0, np.int64))


def test_plan_dispatch_policy():
    arrivals = np.array([0.0, 0.001, 0.002, 0.010, 0.011])
    # full batch available and worker free -> dispatch at the filling arrival
    n, t = plan_dispatch(arrivals, 0, t_free=0.0, max_batch=3, max_wait=0.05)
    assert (n, t) == (3, 0.002)
    # partial batch -> wait out max_wait from the oldest request
    n, t = plan_dispatch(arrivals, 3, t_free=0.0, max_batch=3, max_wait=0.005)
    assert n == 2 and t == pytest.approx(0.015)
    # busy worker: riders accumulate until t_free
    n, t = plan_dispatch(arrivals, 0, t_free=0.02, max_batch=10, max_wait=0.001)
    assert (n, t) == (5, 0.02)
    # never dispatch before the worker is free
    n, t = plan_dispatch(arrivals, 0, t_free=0.5, max_batch=3, max_wait=0.001)
    assert (n, t) == (3, 0.5)


# ---------------------------------------------------------------------------
# online answer correctness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scatter", "tiled"])
@pytest.mark.parametrize("hops", [1, 2])
def test_serve_answer_exact_with_full_fanout(node_setup, backend, hops):
    """SAGE + fanout >= max degree: the sampled MFG covers the entire
    neighborhood, so store-fetch + recompute must equal the offline
    layer-wise logits exactly (same floats, both backends)."""
    g, feats = node_setup
    spec = GNNSpec(model="sage", feature_dim=12, hidden_dim=8, num_classes=5,
                   num_layers=3, agg_backend=backend)
    params = init_params(spec, seed=0)
    owner = partition_vertices(g, 2, "metis", seed=0)
    vbook = build_vertex_book(g, owner, 2)
    eng = LayerwiseInference.build(
        g, edge_assignment_from_vertex(g, owner), 2, spec, params, feats)
    embs = eng.run()
    indptr, _ = g.csr()
    full_fanout = int(np.diff(indptr).max())
    engines, batchers, _ = build_serving(
        g, vbook, spec, params, embs, hops=hops, fanout=full_fanout,
        max_batch=6, seed=0)
    rng = np.random.default_rng(3)
    for w in range(2):
        ids = rng.choice(np.where(owner == w)[0], size=6, replace=False)
        mfg = batchers[w].build_mfg(ids)
        logits, stats, _ = engines[w].answer(mfg)
        np.testing.assert_allclose(logits[:6], embs[-1][ids],
                                   rtol=1e-5, atol=1e-6)
        assert stats.num_input == int(mfg.input_mask.sum())


def test_serve_hops_validation(node_setup):
    g, feats = node_setup
    spec = GNNSpec(model="sage", feature_dim=12, hidden_dim=8, num_classes=5,
                   num_layers=2)
    params = init_params(spec, seed=0)
    owner = partition_vertices(g, 2, "metis", seed=0)
    vbook = build_vertex_book(g, owner, 2)
    eng = LayerwiseInference.build(
        g, edge_assignment_from_vertex(g, owner), 2, spec, params, feats)
    embs = eng.run()
    with pytest.raises(ValueError):
        build_serving(g, vbook, spec, params, embs, hops=2)  # hops == L
    from repro.serve import ServeEngine
    from repro.gnn.sampling import SamplePlan
    stores = build_embedding_stores(g, vbook, embs)
    with pytest.raises(ValueError):  # store dim mismatch (logits store)
        ServeEngine(spec=spec, params=params, store=stores[-1],
                    plan=SamplePlan.build(4, (5,)), hops=1, worker=0)


# ---------------------------------------------------------------------------
# cost model + end-to-end monotonicity
# ---------------------------------------------------------------------------


def test_serve_request_monotone_in_misses():
    spec = GNNSpec(model="sage", feature_dim=64, hidden_dim=64,
                   num_classes=8, num_layers=2)
    kw = dict(spec=spec, embed_dim=64, hops=1)
    base = cost_model.serve_request(200, 80, 0, 1000, **kw)
    prev = base
    for miss in (10, 40, 80):
        est = cost_model.serve_request(200, 80, miss, 1000, **kw)
        assert est.fetch_bytes == miss * 64 * 4
        assert est.service_time > prev.service_time
        assert est.sample_time == base.sample_time  # adjacency unaffected
        assert est.compute_time == base.compute_time
        prev = est
    # forward-only: cheaper than a training step of the same shape
    mb = cost_model.minibatch_step(
        np.array([200.0]), np.array([80.0]), np.array([1000.0]),
        np.array([500.0]), spec)
    assert base.compute_time < float(mb.compute_time[0])


def test_better_partitioner_lowers_modeled_latency(or_graph):
    """The tentpole's claim end to end: metis (low edge-cut) must move
    strictly fewer embedding miss bytes AND deliver lower modeled request
    latency than random partitioning, same trace, same model."""
    g = or_graph
    spec = GNNSpec(model="sage", feature_dim=16, hidden_dim=256,
                   num_classes=8, num_layers=2)
    params = init_params(spec, seed=0)
    rng = np.random.default_rng(11)
    feats = rng.normal(size=(g.num_vertices, 16)).astype(np.float32)
    n, qps = 240, 300.0
    req = rng.integers(0, g.num_vertices, n)
    arr = np.sort(rng.uniform(0, n / qps, n))
    out = {}
    for method in ("random", "metis"):
        owner = partition_vertices(g, 4, method, seed=0)
        vbook = build_vertex_book(g, owner, 4)
        eng = LayerwiseInference.build(
            g, edge_assignment_from_vertex(g, owner), 4, spec, params, feats)
        engines, batchers, _ = build_serving(
            g, vbook, spec, params, eng.run(), hops=1, fanout=10,
            max_batch=16, max_wait=5e-4, seed=0)
        out[method] = run_serving_sim(engines, batchers, owner, req, arr)
    assert out["metis"].fetch.miss_bytes < out["random"].fetch.miss_bytes
    assert (out["metis"].latency.mean() < out["random"].latency.mean())
    assert out["metis"].p50() < out["random"].p50()
    # conservation on the merged accounting
    for rep in out.values():
        f = rep.fetch
        assert f.num_local + f.num_cache_hit + f.num_remote_miss == f.num_input
        assert rep.served() == n


def test_serving_sim_under_load_queues():
    """Offered load far above sustainable must show up as queueing delay
    (latency >> service time), not silently dropped requests."""
    g = generate_graph("social", 120, 500, seed=1)
    spec = GNNSpec(model="sage", feature_dim=8, hidden_dim=8, num_classes=4,
                   num_layers=2)
    params = init_params(spec, seed=0)
    owner = partition_vertices(g, 2, "metis", seed=0)
    vbook = build_vertex_book(g, owner, 2)
    eng = LayerwiseInference.build(
        g, edge_assignment_from_vertex(g, owner), 2, spec, params,
        np.zeros((g.num_vertices, 8), np.float32))
    engines, batchers, _ = build_serving(
        g, vbook, spec, params, eng.run(), hops=1, fanout=5, max_batch=4,
        max_wait=1e-4, seed=0)
    rng = np.random.default_rng(0)
    n = 64
    req = rng.integers(0, g.num_vertices, n)
    arr = np.sort(rng.uniform(0, 1e-3, n))  # effectively simultaneous
    rep = run_serving_sim(engines, batchers, owner, req, arr)
    assert rep.served() == n
    # the last-served requests waited behind ~n/(2 workers * 4 batch) batches
    assert rep.latency.max() > 3 * rep.service_time.mean()
    assert rep.p99() > rep.p50()
