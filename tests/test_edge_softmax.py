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


def _symmetric(rng, *, n, e, heads, dh, reach, hub=None):
    """An edge list that is its own transpose, [a; b] -> [b; a], a tenth of
    its pairs masked (both directions), over rows [0, reach): the rows
    past it are isolated. `hub` joins one row to as many more."""
    a = rng.integers(0, reach, e).astype(np.int32)
    b = rng.integers(0, reach, e).astype(np.int32)
    if hub is not None:
        a = np.concatenate([a, np.zeros(hub, np.int32)])
        b = np.concatenate([b, rng.integers(0, reach, hub).astype(np.int32)])
    keep = rng.random(a.shape[0]) >= 0.1
    z = rng.normal(size=(n, heads * dh)).astype(np.float32)
    att = (rng.normal(size=(heads, dh)) / np.sqrt(dh)).astype(np.float32)
    s_dst = rng.normal(size=(n, heads)).astype(np.float32)
    return (z, att, s_dst, np.concatenate([a, b]), np.concatenate([b, a]),
            np.concatenate([keep, keep]))


def _reverse_pass_grads(z, a, s_dst, src, dst, mask, rows):
    """Gradients of the reverse-pass backward and of the oracle's
    autodiff, for the same cotangents of (den, num); the reverse pass
    counted once."""
    from repro.obs.trace import tracing

    order, ldst, btile, _ = ops.prepare_tiled_edges(dst, rows, valid=mask)
    layout = dict(edge_order=jnp.asarray(order.astype(np.int32)),
                  local_dst=jnp.asarray(ldst), block_tile=jnp.asarray(btile))
    g_rng = np.random.default_rng(2)
    g_den = jnp.asarray(g_rng.normal(size=(rows, a.shape[0])), jnp.float32)
    g_num = jnp.asarray(g_rng.normal(size=(rows, z.shape[1])), jnp.float32)

    half = src.shape[0] // 2

    def grads(softmax):
        def f(z, a, s_dst):
            _, den, num = softmax(z, a, s_dst)
            return (den * g_den).sum() + (num * g_num).sum()

        return jax.grad(f, argnums=(0, 1, 2))(z, a, s_dst)

    want = grads(lambda *x: ops.edge_softmax(
        *x, jnp.asarray(src), jnp.asarray(dst), rows, mask=jnp.asarray(mask)))
    with tracing() as tr:
        got = grads(lambda *x: ops.edge_softmax_symmetric(
            *x, jnp.asarray(src[:half]), jnp.asarray(dst[:half]),
            mask=jnp.asarray(mask[:half]), backend="pallas", **layout))
    assert tr.total("edge.softmax.reverse_pass") == 1
    return btile, got, want


def _assert_grads(got, want):
    for name, x, y in zip(("dz", "da", "ds_dst"), got, want):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


@pytest.mark.parametrize("heads,dh", [(4, 128), (4, 4)])
def test_reverse_pass_matches_oracle(heads, dh):
    """A symmetric list with masked pairs and isolated rows, and a hub row
    whose edges fill several blocks of its row tile, over three row tiles:
    dz, da and ds_dst as the oracle's autodiff gives them."""
    rng = np.random.default_rng(11 * dh)
    rows = 700
    problem = _symmetric(rng, n=rows, e=1200, heads=heads, dh=dh, reach=560,
                         hub=3 * ops.DEFAULT_BLOCK_E)
    btile, got, want = _reverse_pass_grads(*problem, rows)
    assert len(set(btile)) == 3
    assert (np.asarray(btile) == 0).sum() >= 3
    _assert_grads(got, want)
    isolated = np.asarray(got[0])[560:]
    assert (isolated == 0).all()


@pytest.mark.parametrize("heads,dh", [(4, 128), (4, 4)])
def test_reverse_pass_ties_at_zero(heads, dh):
    """Scores exactly 0, where leaky_relu' switches: zero rows with zero
    destination scores tie every score of their edges at 0, and the
    derivative there is 1 in the reverse pass, in the closed-form
    ds_dst and in the oracle's autodiff."""
    rng = np.random.default_rng(13)
    rows = 300
    z, a, s_dst, src, dst, mask = _symmetric(rng, n=rows, e=900, heads=heads,
                                             dh=dh, reach=rows)
    z[::2] = 0.0
    s_dst[::2] = 0.0
    pre = (np.einsum("nhd,hd->nh", z.reshape(rows, heads, dh), a)[src]
           + s_dst[dst])
    assert (pre[mask] == 0).sum() > 100
    _, got, want = _reverse_pass_grads(z, a, s_dst, src, dst, mask, rows)
    _assert_grads(got, want)


def _bf16_mxu(a, b):
    """The MXU's product at the default precision: bfloat16 operands,
    float32 accumulation."""
    return jax.lax.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)


@pytest.mark.parametrize("heads,dh", [(4, 128), (4, 4)])
def test_reverse_pass_error_at_bf16_products(monkeypatch, heads, dh):
    """On a TPU the kernels' default-precision products (P @ z forward,
    w @ g_num backward) take bfloat16 operands. With the interpreted
    kernels' products rounded so, a GAT layer's gradient through the
    reverse pass and the closed-form ds_dst errs from the float32
    oracle's, leaf by leaf (z, a_src, a_dst), by at most 1.5x what the
    chunked loop of float32 per-edge products and scatter-adds into the
    source and destination rows errs, fed by the same rounded forward.
    (w @ g_num in bfloat16 adds to z's error; the closed form, consistent
    with the rounded num it is taken from, takes from a_dst's.)"""
    from repro.kernels import segment_spmm

    rng = np.random.default_rng(19)
    rows, e = 600, 3000
    z, a_src, _, src, dst, mask = _symmetric(rng, n=rows, e=e, heads=heads,
                                             dh=dh, reach=rows)
    a_dst = (rng.normal(size=(heads, dh)) / np.sqrt(dh)).astype(np.float32)
    g_out = jnp.asarray(rng.normal(size=(rows, heads * dh)), jnp.float32)
    order, ldst, btile, _ = ops.prepare_tiled_edges(dst, rows, valid=mask)
    layout = dict(edge_order=jnp.asarray(order.astype(np.int32)),
                  local_dst=jnp.asarray(ldst), block_tile=jnp.asarray(btile))
    src, dst, mask = map(jnp.asarray, (src, dst, mask))

    def layer_grads(softmax):
        """gat_layer's attention and quotient around `softmax`."""
        def loss(z, a_src, a_dst):
            s_dst = ops.attention_scores(z, a_dst)
            e_self = jax.nn.leaky_relu(
                ops.attention_scores(z, a_src) + s_dst, 0.2)
            _, den, num = ops.softmax_merge(softmax(z, a_src, s_dst),
                                            ops.softmax_edge(e_self, z))
            out = num.reshape(rows, heads, dh) / den[:, :, None]
            return (out.reshape(rows, -1) * g_out).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(z, a_src, a_dst)

    def reverse_pass(z, a, s_dst):
        return ops.edge_softmax_symmetric(
            z, a, s_dst, src[:e], dst[:e], mask=mask[:e], backend="pallas",
            **layout)

    @jax.custom_vjp
    def chunk_loop(z, a, s_dst):
        return ops.edge_softmax(z, a, s_dst, src, dst, rows, mask=mask,
                                backend="pallas", **layout)

    def chunk_loop_fwd(z, a, s_dst):
        out = chunk_loop(z, a, s_dst)
        return out, (z, a, s_dst, out)

    def chunk_loop_bwd(res, g):
        z, a, s_dst, (m, den, num) = res
        _, g_den, g_num = g
        table = jnp.concatenate([g_num, s_dst, m, g_den], axis=1)
        dz, da = ops._softmax_chunks(rows, z, a, table, src, dst, mask)
        pre = ops.attention_scores(z, a)[src] + s_dst[dst]
        w = jnp.exp(jnp.where(mask[:, None],
                              jax.nn.leaky_relu(pre, 0.2) - m[dst], -jnp.inf))
        gz = jnp.einsum("ehd,ehd->eh", g_num[dst].reshape(-1, heads, dh),
                        z[src].reshape(-1, heads, dh))
        ds = w * (gz + g_den[dst]) * jnp.where(pre >= 0, 1.0, 0.2)
        return dz, da, jnp.zeros_like(s_dst).at[dst].add(ds)

    chunk_loop.defvjp(chunk_loop_fwd, chunk_loop_bwd)
    want = layer_grads(lambda z, a, s_dst: ops.edge_softmax(
        z, a, s_dst, src, dst, rows, mask=mask))
    monkeypatch.setattr(segment_spmm, "_mxu", _bf16_mxu)
    got = layer_grads(reverse_pass)
    old = layer_grads(chunk_loop)
    for name, x, y, w in zip(("z", "a_src", "a_dst"), got, old, want):
        err, err_old = (float(jnp.linalg.norm(v - w) / jnp.linalg.norm(w))
                        for v in (x, y))
        assert 1e-5 < err_old < 1e-2, (name, err_old)
        assert err <= 1.5 * err_old, (name, err, err_old)


def test_kernel_keeps_the_backward_sums_only_for_a_gradient():
    """The forward alone runs the three-output kernel; the forward of a
    gradient the five-output one, and the reverse pass its two."""
    rng = np.random.default_rng(23)
    z, a, s_dst, src, dst, mask = _symmetric(rng, n=300, e=600, heads=4,
                                             dh=4, reach=300)
    order, ldst, btile, _ = ops.prepare_tiled_edges(dst, 300, valid=mask)

    def forward(z):
        return ops.edge_softmax_symmetric(
            z, jnp.asarray(a), jnp.asarray(s_dst), jnp.asarray(src[:600]),
            jnp.asarray(dst[:600]), mask=jnp.asarray(mask[:600]),
            edge_order=jnp.asarray(order.astype(np.int32)),
            local_dst=jnp.asarray(ldst), block_tile=jnp.asarray(btile),
            backend="pallas")

    def outputs(jaxpr):
        """Output counts of the pallas calls, nested jaxprs included."""
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(len(eqn.outvars))
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    found += outputs(sub)
        return found

    assert outputs(jax.make_jaxpr(forward)(z).jaxpr) == [3]
    grad = jax.grad(lambda z: forward(z)[2].sum())
    assert sorted(outputs(jax.make_jaxpr(grad)(z).jaxpr)) == [2, 5]


@pytest.mark.parametrize("heads,dh", [(4, 128), (4, 4)])
def test_closed_form_score_grad_on_a_chunk_loop(heads, dh):
    """A list that is not its own transpose (a ring chunk's shape: sources
    in one block of rows, destinations in another) keeps the chunked
    loop, with ds_dst now in closed form from the forward's sums; some
    scores sit exactly at 0."""
    rng = np.random.default_rng(17)
    rows, e = 400, 2500
    z, a, s_dst, _, _, _ = _problem(rng, n=rows, rows=rows, e=e, heads=heads,
                                    dh=dh)
    src = rng.integers(200, rows, e).astype(np.int32)
    dst = rng.integers(0, 200, e).astype(np.int32)
    mask = rng.random(e) >= 0.1
    z[200::3] = 0.0
    s_dst[::3] = 0.0
    _compare(z, a, s_dst, src, dst, mask, rows)


def test_closed_form_score_grad_on_a_minibatch_block():
    """The mini-batch MFGs are not their own transpose: GAT's mini-batch
    loss on the interpreted kernel takes no reverse pass, and its
    gradients (ds_dst in closed form) match the scatter oracle's."""
    from repro.core.vertex_partition import partition_vertices
    from repro.gnn.minibatch import MiniBatchTrainer, minibatch_loss
    from repro.obs.trace import tracing

    from repro.core.graph import paper_graph

    g = paper_graph("OR", scale=0.02, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 16)).astype(np.float32)
    labels = rng.integers(0, 5, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    owner = partition_vertices(g, 4, "metis", seed=0)
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   agg_backend="pallas")
    tr = MiniBatchTrainer.build(g, owner, 4, spec, feats, labels, train,
                                global_batch=64, seed=3)
    pb = tr.engine.preparer.prepare()
    batch0 = jax.tree.map(lambda a: a[0], pb.stacked)
    sizes = tuple(tr._layer_sizes)
    grads = {}
    for backend in ("scatter", "pallas"):
        sp = dataclasses.replace(spec, agg_backend=backend)
        with tracing() as t:
            grads[backend] = jax.grad(
                lambda p: minibatch_loss(sp, p, batch0, sizes, axis=None)
            )(tr.params)
        assert t.total("edge.softmax.reverse_pass") is None
    assert t.total("edge.softmax.calls") == spec.num_layers
    for x, y in zip(jax.tree.leaves(grads["pallas"]),
                    jax.tree.leaves(grads["scatter"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.parametrize("sync", ["dense", "halo"])
def test_gat_reverse_pass_k4_grads_equal_k1(or_graph, node_data, sync):
    """Each partition's edge list is its own transpose, so at k = 4 (sim)
    every partition's backward is a reverse pass: the step's gradient
    equals that of k = 1, leaf by leaf."""
    feats, labels, train = node_data
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   num_layers=2, agg_backend="pallas")
    ref = FullBatchTrainer.build(
        or_graph, np.zeros(or_graph.num_edges, np.int32), 1, spec, feats,
        labels, train, seed=7)
    tr = FullBatchTrainer.build(
        or_graph, partition_edges(or_graph, 4, "hdrf", seed=1), 4, spec,
        feats, labels, train, sync_mode=sync, mode="sim", seed=7)

    def grads(t):
        loss, _ = t._step_fns
        return jax.grad(lambda p: jnp.mean(t._wrap(loss)(p, t.blocks)))(
            t.params)

    for a, b in zip(jax.tree.leaves(grads(tr)), jax.tree.leaves(grads(ref))):
        assert np.abs(np.asarray(b)).max() > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)


@pytest.mark.parametrize("sync,passes", [("local", 3), ("dense", 3),
                                         ("halo", 3), ("ring", None)])
def test_reverse_pass_counted_in_a_gat_step(or_graph, node_data, sync,
                                            passes):
    """`edge.softmax.reverse_pass` counts the backwards jax traces that
    take the reverse pass: one a layer of GAT's full-batch step under the
    partial-aggregate syncs, none under ring, whose chunks are not their
    own transpose."""
    from repro.obs.trace import tracing

    feats, labels, train = node_data
    spec = GNNSpec(model="gat", feature_dim=16, hidden_dim=8, num_classes=5,
                   num_layers=3, agg_backend="pallas")
    k = 1 if sync == "local" else 4
    asg = (np.zeros(or_graph.num_edges, np.int32) if k == 1 else
           None if sync == "ring" else
           partition_edges(or_graph, 4, "hdrf", seed=1))
    kw = {} if k == 1 else dict(sync_mode=sync, mode="sim")
    tr = FullBatchTrainer.build(or_graph, asg, k, spec, feats, labels, train,
                                **kw)
    with tracing() as t:
        tr.train_step()
    assert t.total("edge.softmax.calls") > 0
    assert t.total("edge.softmax.reverse_pass") == passes


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
