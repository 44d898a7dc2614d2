"""GAT's one-pass edge softmax: the kernel's softmax combiner and the op
around it (`ops.edge_softmax`, interpreted on the CPU) against the scatter
oracle, values and gradients; its partials completed by every sync
strategy; the full-batch GAT step on the kernel against the plain float32
reference of the benchmark; and the readers of its benchmark metrics."""

import dataclasses
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.edge_partition import partition_edges
from repro.gnn.fullbatch import FullBatchTrainer
from repro.gnn.models import GNNSpec
from repro.kernels import ops


def _problem(rng, *, n, rows, e, heads, dh, dst=None, mask=None):
    z = rng.normal(size=(n, heads * dh)).astype(np.float32)
    a = (rng.normal(size=(heads, dh)) / np.sqrt(dh)).astype(np.float32)
    s_dst = rng.normal(size=(rows, heads)).astype(np.float32)
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, rows, e).astype(np.int32) if dst is None else dst
    mask = np.ones(e, bool) if mask is None else mask
    return z, a, s_dst, src, dst, mask


def _compare(z, a, s_dst, src, dst, mask, rows):
    """Values and gradients of the kernel path against the oracle's."""
    order, ldst, btile, _ = ops.prepare_tiled_edges(dst, rows, valid=mask)
    layout = dict(edge_order=jnp.asarray(order.astype(np.int32)),
                  local_dst=jnp.asarray(ldst), block_tile=jnp.asarray(btile))
    idx = (jnp.asarray(src), jnp.asarray(dst))
    g_rng = np.random.default_rng(1)
    g_den = jnp.asarray(g_rng.normal(size=(rows, a.shape[0])), jnp.float32)
    g_num = jnp.asarray(g_rng.normal(size=(rows, z.shape[1])), jnp.float32)

    def run(backend):
        def f(z, a, s_dst):
            m, den, num = ops.edge_softmax(
                z, a, s_dst, *idx, rows, mask=jnp.asarray(mask),
                backend=backend, **layout)
            return (den * g_den).sum() + (num * g_num).sum(), (m, den, num)

        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                             has_aux=True)(z, a, s_dst)
        return out, grads

    want, want_g = run("scatter")
    got, got_g = run("pallas")
    for name, x, y in zip(("m", "den", "num"), got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-5, err_msg=name)
    for name, x, y in zip(("z", "a_src", "s_dst"), got_g, want_g):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-4, err_msg=name)
    return got


@pytest.mark.parametrize("heads,dh", [(4, 128), (4, 4)])
def test_kernel_matches_oracle(heads, dh):
    """Random edges, a tenth of them masked out (the layout drops them),
    and rows no edge reaches: those come back as (floor, 0, 0)."""
    rng = np.random.default_rng(heads * dh)
    rows = 600
    e = 3000
    dst = rng.integers(0, 400, e).astype(np.int32)   # rows 400.. isolated
    mask = rng.random(e) >= 0.1
    m, den, num = _compare(*_problem(rng, n=500, rows=rows, e=e, heads=heads,
                                     dh=dh, dst=dst, mask=mask), rows)
    reached = np.zeros(rows, bool)
    reached[dst[mask]] = True
    assert (np.asarray(m)[~reached] == ops.SOFTMAX_FLOOR).all()
    assert (np.asarray(den)[~reached] == 0).all()
    assert (np.asarray(num)[~reached] == 0).all()
    assert (np.asarray(den)[reached] >= 1 - 1e-6).all()


@pytest.mark.parametrize("heads,dh", [(4, 128), (4, 4)])
def test_kernel_tied_scores(heads, dh):
    """Sources with equal rows tie every score of a row: the weights split
    evenly, as the oracle's."""
    rng = np.random.default_rng(3)
    z, a, s_dst, _, dst, mask = _problem(rng, n=8, rows=256, e=1500,
                                         heads=heads, dh=dh)
    z[:] = z[0]
    src = rng.integers(0, 8, 1500).astype(np.int32)
    m, den, num = _compare(z, a, s_dst, src, dst, mask, 256)
    count = np.bincount(dst, minlength=256)
    np.testing.assert_allclose(np.asarray(den)[:, 0], count, rtol=1e-5)


@pytest.mark.parametrize("heads,dh", [(4, 128), (4, 4)])
def test_kernel_rescales_rising_maxima(heads, dh):
    """All edges into one row tile, in 4 edge blocks whose scores rise from
    block to block by far more than exp's range: each block raises the
    running max, and the resident accumulators must be rescaled."""
    rng = np.random.default_rng(5)
    e = 4 * ops.DEFAULT_BLOCK_E
    z, a, s_dst, _, _, mask = _problem(rng, n=e, rows=256, e=e, heads=heads,
                                       dh=dh)
    src = np.arange(e, dtype=np.int32)
    dst = rng.integers(0, 256, e).astype(np.int32)
    # source score a_h . z_h = z[:, h*dh] rises with the edge's index
    a[:, 0] = 1.0
    a[:, 1:] = 0.0
    z[:, ::dh] = np.linspace(0.0, 400.0, e, dtype=np.float32)[:, None]
    s_dst[:] = 0.0
    order, _, btile, _ = ops.prepare_tiled_edges(dst, 256)
    assert list(btile) == [0, 0, 0, 0]
    assert (np.diff(order[order < e]) > 0).all()
    m, den, num = _compare(z, a, s_dst, src, dst, mask, 256)
    assert np.isfinite(np.asarray(num)).all()


@pytest.mark.parametrize("backend", ["scatter", "pallas"])
@pytest.mark.parametrize("sync", ["dense", "halo", "ring"])
def test_gat_sync_k4_equals_k1(or_graph, node_data, sync, backend):
    """Every strategy completes the edge softmax's partials at k = 4 (sim)
    to the k = 1 result: forward logits and a training step."""
    feats, labels, train = node_data
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   num_layers=2, agg_backend=backend)
    ref = FullBatchTrainer.build(
        or_graph, np.zeros(or_graph.num_edges, np.int32), 1, spec, feats,
        labels, train, seed=7)
    asg = None if sync == "ring" else partition_edges(or_graph, 4, "hdrf",
                                                      seed=1)
    tr = FullBatchTrainer.build(or_graph, asg, 4, spec, feats, labels, train,
                                sync_mode=sync, mode="sim", seed=7)
    np.testing.assert_allclose(tr.forward_logits_global(),
                               ref.forward_logits_global(), rtol=2e-4,
                               atol=2e-4)
    for _ in range(2):
        assert abs(tr.train_step() - ref.train_step()) < 1e-4


@pytest.mark.parametrize("sync,calls", [("halo", 2), ("ring", 8)])
def test_edge_softmax_calls_counted_when_traced(or_graph, node_data, sync,
                                                calls):
    """`edge.softmax.calls` counts the edge softmaxes jax traces while a
    tracer is enabled: one a layer, one a ring stage; none without."""
    from repro.obs.trace import tracing

    feats, labels, train = node_data
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   num_layers=2)
    asg = None if sync == "ring" else partition_edges(or_graph, 4, "hdrf",
                                                      seed=1)

    def build():
        return FullBatchTrainer.build(or_graph, asg, 4, spec, feats, labels,
                                      train, sync_mode=sync, mode="sim")

    with tracing() as tr:
        build().forward_logits_global()
    assert tr.total("edge.softmax.calls") == calls
    with tracing() as tr:
        pass
    build().forward_logits_global()
    assert tr.total("edge.softmax.calls") is None


def test_gat_ring_moves_z_once_a_layer():
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   num_layers=2, gat_heads=4)
    assert spec.aggregate_dims("ring") == [[8], [4]]
    assert spec.aggregate_dims("halo") == [[4, 4, 8], [4, 4, 4]]


def test_gat_fullbatch_pallas_matches_reference():
    """The GAT cell's full-batch step, k = 1, on the interpreted kernel,
    against the plain float32 reference (`bench/reference/gat.py`) from
    the same seeded weights at a tiny size: the three checked steps'
    losses, the first gradient and the update."""
    from bench import harness, tiny

    cell = tiny.cell("gat4.fullbatch.or", scale=0.01)
    cell = dataclasses.replace(
        cell, config={**cell.config, "agg_backend": "pallas"})
    out = harness.run(cell, 2 ** 31 + 5, 0.1, False, time.perf_counter(),
                      jax.devices()[0])
    assert out["correct"], out["checks"]
    for key in ("loss_gap", "grad_gap", "update_gap"):
        assert out["checks"][key]["value"] < 1e-4, out["checks"]


def _softmax_run(events, kernel_calls, model="gat"):
    from bench import cells

    config = cells.resolve("gat4.fullbatch.or").config
    return SimpleNamespace(
        cell=SimpleNamespace(config={**config, "model": model},
                             traffic={"regime": "fullbatch"}),
        trace=SimpleNamespace(kernel_events=events),
        kernel_calls=kernel_calls, num_messages=2684556, num_vertices=25000,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _event(i, seconds):
    return (f"%segment_spmm_softmax.{i} = (f32[25088,512]{{1,0}}) "
            f"custom-call(%a)", seconds)


def test_edge_softmax_readers_match_a_hand_count():
    """Two steps of three calls: 6.79 ms of least time for each 512 wide
    layer and 0.23 ms for the 16 wide last one, bytes bound."""
    from bench import cells

    rl = cells.reader("edge_softmax_roofline").__globals__
    assert rl["widths"]({"gat_heads": 4, "hidden_dim": 512, "num_layers": 3,
                         "num_classes": 16}) == [(4, 512), (4, 512), (4, 16)]
    # 10 messages, 3 rows, 2 heads of width 2: 2*10*4 + 10*2 flops; read
    # 10x4 floats and 10 indices, 3 rows of 4 + 4*2
    assert rl["call_work"](10, 3, 2, 4) == (100, 4 * (40 + 10 + 3 * 12))
    events = [_event(i, s) for i, s in
              enumerate([0.02, 0.02, 0.001, 0.02, 0.02, 0.001])]
    run = _softmax_run(events, 3)
    least = (2 * 4 * (2684556 * 512 + 2684556 + 25000 * (512 + 16))
             + 4 * (2684556 * 16 + 2684556 + 25000 * (16 + 16))) / 819e9
    assert least == pytest.approx(0.0138, abs=1e-4)
    roofline = cells.reader("edge_softmax_roofline")(run)
    assert roofline == pytest.approx(100 * least / 0.041)
    assert cells.reader("edge_softmax.kernel_s")(run) == pytest.approx(0.041)
    # silent on a partial step, a foreign kernel, another model, no trace
    for bad in (_softmax_run(events[:5], 3),
                _softmax_run(events[:5] + [("%segment_spmm_sum.3 = f32[8,"
                                            "512] custom-call(%a)", 0.01)], 3),
                _softmax_run(events, 3, model="sage"),
                _softmax_run(events, 6)):
        for name in ("edge_softmax_roofline", "edge_softmax.kernel_s"):
            assert cells.reader(name)(bad) is None
    run.trace = None
    assert cells.reader("edge_softmax_roofline")(run) is None
