"""The wire-codec layer (core/wire.py) and its four byte-moving paths.

Three families of pins:

  algebra    — each codec's roundtrip error bound, the wire_bytes ==
               encoded-payload-nbytes property, the variable-ratio schedule,
               and the error-feedback telescoping identity (under vmap here;
               the real-shard_map twin lives in the subprocess test below)
  identity   — `codec="fp32"` is the exact identity on every path: trainers
               (halo/ring full-batch, mini-batch), feature store, cost model
               produce BITWISE-identical results vs codec=None
  tolerance  — int8+EF 20-step loss trajectories stay within a pinned
               tolerance of fp32 for sage/gcn/gat x halo/ring and mini-batch
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import paper_graph
from repro.core.edge_partition import partition_edges
from repro.core.vertex_partition import partition_vertices
from repro.core.wire import (
    CODECS,
    Fp32Codec,
    VariableRatioCodec,
    as_codec,
    codec_grad_reduce,
    ef_init,
    make_codec,
    roundtrip,
)
from repro.gnn.models import GNNSpec


@pytest.fixture(scope="module")
def wg():
    """Small graph + node data shared by the end-to-end codec tests."""
    g = paper_graph("OR", scale=0.01, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 8)).astype(np.float32)
    labels = rng.integers(0, 4, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    return g, feats, labels, train


def _spec(model="sage"):
    return GNNSpec(model=model, feature_dim=8, hidden_dim=8, num_classes=4)


# ---------------------------------------------------------------------------
# codec algebra
# ---------------------------------------------------------------------------


def test_registry_and_normalisation():
    for name in CODECS:
        assert make_codec(name).name == name
    assert isinstance(as_codec(None), Fp32Codec)
    assert as_codec("int8") is make_codec("int8")
    c = make_codec("bf16")
    assert as_codec(c) is c
    with pytest.raises(ValueError, match="unknown codec"):
        make_codec("fp8")


@pytest.mark.parametrize("to_dev", [False, True])
def test_fp32_is_the_exact_identity(to_dev):
    """encode/decode return their argument UNTOUCHED — same object, so the
    default paths cannot even in principle perturb bytes or the jaxpr."""
    x = np.random.default_rng(1).normal(size=(7, 5)).astype(np.float32)
    if to_dev:
        x = jnp.asarray(x)
    c = make_codec("fp32")
    payload, meta = c.encode(x)
    assert payload is x and meta is None
    assert c.decode(payload, meta) is x
    assert c.wire_bytes(x.shape) == x.size * 4
    assert c.ratio(0) == c.ratio(3) == 1.0


def test_wire_dtype_policy_via_analysis(wg):
    """`narrow_wire_dtypes` declares each codec's on-wire narrow dtypes and
    the gnn_lint dtype-policy check holds traced train steps to exactly
    those: the fp32 step contains NO narrowing convert anywhere in its
    jaxpr, the int8 step narrows to s8 only — the jaxpr-level twin of the
    bitwise-identity pins below."""
    from repro.analysis import check_narrowing
    from repro.core.wire import narrow_wire_dtypes
    from repro.gnn.fullbatch import FullBatchTrainer

    assert narrow_wire_dtypes("fp32") == frozenset()
    assert narrow_wire_dtypes("bf16") == frozenset({"bfloat16"})
    assert narrow_wire_dtypes("int8") == frozenset({"int8"})
    assert narrow_wire_dtypes("variable")  # schedules are never identity
    assert narrow_wire_dtypes("variable") <= {"int8", "bfloat16"}

    g, feats, labels, train = wg
    jaxprs = {}
    for codec in ("fp32", "int8"):
        tr = FullBatchTrainer.build(g, None, 4, _spec(), feats, labels,
                                    train, sync_mode="ring", mode="sim",
                                    seed=7, codec=codec)
        loss, _ = tr._step_fns
        jaxprs[codec] = jax.make_jaxpr(tr._wrap(loss))(tr.params, tr.blocks)
    assert check_narrowing([jaxprs["fp32"]], "fp32") == []
    assert check_narrowing([jaxprs["int8"]], "int8") == []
    # the int8 trace genuinely narrows (f32 -> s8 on the wire), so the
    # clean fp32 result above is not the walker being blind
    assert check_narrowing([jaxprs["int8"]], "fp32")


@pytest.mark.parametrize("to_dev", [False, True])
def test_bf16_roundtrip_relative_bound(to_dev):
    x = np.random.default_rng(2).normal(size=(64, 9)).astype(np.float32)
    if to_dev:
        x = jnp.asarray(x)
    y = np.asarray(roundtrip(make_codec("bf16"), x))
    rel = np.abs(y - np.asarray(x)) / np.maximum(np.abs(np.asarray(x)), 1e-12)
    # half a ulp of the 8-bit bf16 significand
    assert rel.max() <= 2.0 ** -8 + 1e-7


@pytest.mark.parametrize("to_dev", [False, True])
def test_int8_roundtrip_absolute_bound(to_dev):
    x = np.random.default_rng(3).normal(size=(33, 17)).astype(np.float32)
    if to_dev:
        x = jnp.asarray(x)
    c = make_codec("int8")
    payload, meta = c.encode(x)
    assert np.asarray(payload).dtype == np.int8
    y = np.asarray(c.decode(payload, meta))
    # uniform quantisation: error <= half a step of scale = max|x|/127
    bound = np.abs(np.asarray(x)).max() / 127.0 * 0.5 + 1e-6
    assert np.abs(y - np.asarray(x)).max() <= bound


@pytest.mark.parametrize("name", ["fp32", "bf16", "int8"])
@pytest.mark.parametrize("shape", [(5,), (3, 4), (2, 3, 5), (128, 16)])
@pytest.mark.parametrize("to_dev", [False, True])
def test_wire_bytes_equals_encoded_nbytes(name, shape, to_dev):
    """The analytic `wire_bytes(shape)` IS the encoded representation's size:
    payload.nbytes + meta.nbytes, for numpy and jax inputs alike."""
    c = make_codec(name)
    x = np.random.default_rng(5).normal(size=shape).astype(np.float32)
    if to_dev:
        x = jnp.asarray(x)
    payload, meta = c.encode(x)
    measured = np.asarray(payload).nbytes
    if meta is not None:
        measured += np.asarray(meta).nbytes
    assert c.wire_bytes(shape) == measured


@pytest.mark.parametrize("name", ["fp32", "bf16", "int8"])
def test_wire_bytes_empty_tensor_is_zero(name):
    # nothing crosses the wire for an empty tensor (no scale either)
    assert make_codec(name).wire_bytes((0, 16)) == 0


def test_variable_ratio_schedule():
    c = make_codec("variable")
    assert isinstance(c, VariableRatioCodec)
    # warmup (epoch 0 < warmup_epochs=2): one notch softer everywhere
    assert (c.ratio(0), c.ratio(1), c.ratio(2)) == (0.5, 1.0, 1.0)
    hard = c.at_epoch(2)
    assert hard is not c and c.epoch == 0  # at_epoch builds a NEW codec
    assert (hard.ratio(0), hard.ratio(1)) == (0.25, 0.5)
    # wire_bytes follows the per-layer tier
    assert hard.wire_bytes((10, 4), layer=0) == 10 * 4 + 4      # int8 + scale
    assert hard.wire_bytes((10, 4), layer=1) == 10 * 4 * 2      # bf16
    assert c.wire_bytes((10, 4), layer=1) == 10 * 4 * 4         # warmup fp32
    # decode dispatches on the payload dtype, per sub-codec
    x = jnp.asarray(np.random.default_rng(7).normal(size=(6, 3)),
                    dtype=jnp.float32)
    p0, m0 = hard.encode(x, layer=0)
    assert p0.dtype == jnp.int8
    assert np.abs(np.asarray(hard.decode(p0, m0)) - np.asarray(x)).max() < 0.1
    p1, m1 = hard.encode(x, layer=1)
    assert p1.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(hard.decode(p1, m1)),
                               np.asarray(x), rtol=2.0 ** -8 + 1e-7)


# ---------------------------------------------------------------------------
# error-feedback gradient reduction
# ---------------------------------------------------------------------------


def _lane_grads(rng, k, steps):
    return [{"w": rng.normal(size=(k, 6, 5)).astype(np.float32),
             "b": rng.normal(size=(k, 5)).astype(np.float32)}
            for _ in range(steps)]


def test_fp32_grad_reduce_is_plain_pmean_under_vmap():
    k = 4
    g = _lane_grads(np.random.default_rng(11), k, 1)[0]
    ef = ef_init(g)
    fn = jax.vmap(lambda gr, e: codec_grad_reduce(make_codec("fp32"), gr, e,
                                                  "parts"),
                  axis_name="parts")
    mean, new_ef = fn(g, ef)
    for leaf, got in zip(jax.tree.leaves(g), jax.tree.leaves(mean)):
        # pmean's summation order may differ from numpy's by float rounding
        np.testing.assert_allclose(np.asarray(got),
                                   np.broadcast_to(leaf.mean(0), leaf.shape),
                                   atol=1e-6)
    for e in jax.tree.leaves(new_ef):  # lossless: EF stays zero forever
        assert not np.asarray(e).any()


def test_int8_ef_telescoping_bias_bound_under_vmap():
    """The EF invariant: summed over T steps, the reduced gradients equal the
    true mean-gradient sum minus only the FINAL residual — compression error
    does not accumulate with T (it acts like one delayed gradient)."""
    k, steps = 4, 20
    seq = _lane_grads(np.random.default_rng(12), k, steps)
    codec = make_codec("int8")
    fn = jax.jit(jax.vmap(lambda gr, e: codec_grad_reduce(codec, gr, e,
                                                          "parts"),
                          axis_name="parts"))
    ef = ef_init(seq[0])
    out_sum = {key: 0.0 for key in seq[0]}
    for g in seq:
        mean, ef = fn(g, ef)
        for key in out_sum:
            out_sum[key] = out_sum[key] + np.asarray(mean[key])[0]
    for key in out_sum:
        true_sum = sum(np.asarray(g[key]).mean(0) for g in seq)
        resid = np.asarray(ef[key]).mean(0)
        # exact telescoping identity (up to f32 accumulation)
        np.testing.assert_allclose(out_sum[key], true_sum - resid, atol=1e-3)
        # and the residual is one quantisation step, independent of T
        step_bound = max(np.abs(np.asarray(g[key])).max() for g in seq)
        step_bound = 1.5 * step_bound / 127.0
        assert np.abs(resid).max() <= step_bound
        assert np.abs(out_sum[key] - true_sum).max() <= step_bound


def test_int8_ef_grad_reduce_shard_map_matches_vmap():
    """The same EF reduce under REAL shard_map over 4 devices is numerically
    identical to the vmap simulation, step for step."""
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.wire import make_codec, ef_init, codec_grad_reduce

        k, steps = 4, 6
        rng = np.random.default_rng(0)
        seq = [{"w": rng.normal(size=(k, 6, 5)).astype(np.float32),
                "b": rng.normal(size=(k, 5)).astype(np.float32)}
               for _ in range(steps)]
        codec = make_codec("int8")

        def reduce_lane(g, e):
            return codec_grad_reduce(codec, g, e, "parts")

        vfn = jax.jit(jax.vmap(reduce_lane, axis_name="parts"))
        mesh = jax.make_mesh((k,), ("parts",))
        sfn = jax.jit(jax.shard_map(reduce_lane, mesh=mesh,
                                    in_specs=(P("parts"), P("parts")),
                                    out_specs=(P("parts"), P("parts")),
                                    check_vma=False))

        ef_v, ef_s = ef_init(seq[0]), ef_init(seq[0])
        maxerr = 0.0
        for g in seq:
            mv, ef_v = vfn(g, ef_v)
            ms, ef_s = sfn(g, ef_s)
            for a, b in zip(jax.tree.leaves((mv, ef_v)),
                            jax.tree.leaves((ms, ef_s))):
                maxerr = max(maxerr,
                             float(np.abs(np.asarray(a) - np.asarray(b)).max()))
        print("maxerr", maxerr)
        assert maxerr < 1e-5, maxerr
    """
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=900,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "maxerr" in proc.stdout


# ---------------------------------------------------------------------------
# fp32 is bitwise-identical on every path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sync", ["halo", "ring"])
def test_fp32_codec_bitwise_identical_fullbatch(wg, sync):
    from repro.gnn.fullbatch import FullBatchTrainer

    g, feats, labels, train = wg
    a = None if sync == "ring" else partition_edges(g, 4, "hep100", seed=1)
    trainers = [
        FullBatchTrainer.build(g, a, 4, _spec(), feats, labels, train,
                               sync_mode=sync, mode="sim", seed=7,
                               codec=codec)
        for codec in (None, "fp32")
    ]
    for _ in range(3):
        losses = [tr.train_step() for tr in trainers]
        assert losses[0] == losses[1], losses
    for p0, p1 in zip(jax.tree.leaves(trainers[0].params),
                      jax.tree.leaves(trainers[1].params)):
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))


def test_fp32_codec_bitwise_identical_minibatch(wg):
    from repro.gnn.minibatch import MiniBatchTrainer

    g, feats, labels, train = wg
    a = partition_vertices(g, 4, "metis", seed=1)
    trainers = [
        MiniBatchTrainer.build(g, a, 4, _spec(), feats, labels, train,
                               global_batch=32, seed=7, codec=codec)
        for codec in (None, "fp32")
    ]
    for _ in range(3):
        m0, m1 = (tr.train_step() for tr in trainers)
        assert m0.loss == m1.loss
        np.testing.assert_array_equal(m0.wire_bytes, m0.miss_bytes)
    for p0, p1 in zip(jax.tree.leaves(trainers[0].params),
                      jax.tree.leaves(trainers[1].params)):
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))


def test_fp32_codec_bitwise_identical_feature_store(wg):
    from repro.gnn.feature_store import FeatureStore
    from repro.core.partition_book import build_vertex_book

    g, feats, _, _ = wg
    a = partition_vertices(g, 4, "metis", seed=1)
    book = build_vertex_book(g, a, 4)
    ids = np.random.default_rng(9).integers(0, g.num_vertices, 200)
    stores = [FeatureStore.build(g, book, policy="degree", budget=16,
                                 features=feats, codec=codec)
              for codec in (None, "fp32")]
    for w in range(4):
        blocks, stats = zip(*(s.gather(w, ids) for s in stores))
        np.testing.assert_array_equal(blocks[0], blocks[1])
        assert stats[0] == stats[1]
        assert stats[0].wire_bytes == stats[0].miss_bytes


def test_int8_feature_store_roundtrips_only_miss_rows(wg):
    """Lossy stores perturb exactly the rows that cross the network: local
    and cache-hit rows stay bitwise, misses carry the int8 roundtrip."""
    from repro.gnn.feature_store import FeatureStore
    from repro.core.partition_book import build_vertex_book

    g, feats, _, _ = wg
    a = partition_vertices(g, 4, "metis", seed=1)
    book = build_vertex_book(g, a, 4)
    ids = np.random.default_rng(10).integers(0, g.num_vertices, 200)
    exact = FeatureStore.build(g, book, policy="degree", budget=16,
                               features=feats)
    lossy = FeatureStore.build(g, book, policy="degree", budget=16,
                               features=feats, codec="int8")
    w = 0
    b_exact, s_exact = exact.gather(w, ids)
    b_lossy, s_lossy = lossy.gather(w, ids)
    local, hit, miss = lossy.split(w, ids)
    assert miss.sum() > 0  # the pin below must actually bite
    np.testing.assert_array_equal(b_exact[local], b_lossy[local])
    np.testing.assert_array_equal(b_exact[hit], b_lossy[hit])
    miss_err = np.abs(b_exact[miss] - b_lossy[miss]).max()
    bound = np.abs(b_exact[miss]).max() / 127.0 * 0.5 + 1e-6
    assert 0.0 < miss_err <= bound
    # the split and logical accounting are codec-independent
    assert s_exact._replace(wire_bytes=0) == s_lossy._replace(wire_bytes=0)
    nm, d = int(miss.sum()), feats.shape[1]
    assert s_lossy.wire_bytes == nm * d + 4
    assert s_exact.wire_bytes == s_exact.miss_bytes == nm * d * 4


def test_fetchstats_merge_empty_is_the_zero_record():
    from repro.gnn.feature_store import FetchStats

    z = FetchStats.merge([])
    assert z == FetchStats(0, 0, 0, 0, 0, 0, 0, 0)
    assert z.num_remote == 0 and z.hit_rate == 1.0
    a = FetchStats(10, 5, 3, 2, 500, 300, 200, 54)
    b = FetchStats(4, 4, 0, 0, 400, 0, 0, 0)
    m = FetchStats.merge([a, b])
    assert m.num_input == 14 and m.miss_bytes == 200 and m.wire_bytes == 54


# ---------------------------------------------------------------------------
# int8 loss trajectories stay within tolerance of fp32
# ---------------------------------------------------------------------------

LOSS_TOL = 0.05       # mini-batch: only gradients + feature misses are lossy
LOSS_TOL_FULL = 0.1   # full-batch: the activation exchange quantises too


# GAT over ring is the one combination where naive int8 payloads bias
# training: the ring rotates PRE-message payloads, so exp() is applied to
# quantised attention scores — a convex function of the noise, i.e. a
# systematic softmax bias (halo quantises the post-exp partial sums and is
# fine). That is precisely the case the SAR-style variable ramp exists
# for: its hard tier keeps int8 on the max ordinal and bf16 on the
# exp-bearing ones, and tracks fp32 — so that is the codec pinned there.
@pytest.mark.parametrize("model,sync,codec", [
    ("sage", "halo", "int8"),
    ("sage", "ring", "int8"),
    ("gcn", "halo", "int8"),
    ("gcn", "ring", "int8"),
    ("gat", "halo", "int8"),
    ("gat", "ring", "variable"),
])
def test_lossy_loss_trajectory_fullbatch(wg, model, sync, codec):
    from repro.gnn.fullbatch import FullBatchTrainer

    g, feats, labels, train = wg
    a = None if sync == "ring" else partition_edges(g, 4, "hep100", seed=1)
    if codec == "variable":
        codec = make_codec("variable").at_epoch(2)  # post-warmup (hard) tier
    ref, lossy = (
        FullBatchTrainer.build(g, a, 4, _spec(model), feats, labels, train,
                               sync_mode=sync, mode="sim", seed=7, lr=5e-2,
                               codec=c)
        for c in ("fp32", codec)
    )
    traj_ref = [ref.train_step() for _ in range(20)]
    traj_lossy = [lossy.train_step() for _ in range(20)]
    dev = max(abs(a - b) for a, b in zip(traj_ref, traj_lossy))
    assert dev < LOSS_TOL_FULL, dev
    assert traj_lossy[-1] < traj_lossy[0]  # compression didn't stall training


def test_int8_loss_trajectory_minibatch(wg):
    from repro.gnn.minibatch import MiniBatchTrainer

    g, feats, labels, train = wg
    a = partition_vertices(g, 4, "metis", seed=1)
    ref, lossy = (
        MiniBatchTrainer.build(g, a, 4, _spec(), feats, labels, train,
                               global_batch=32, seed=7, lr=5e-2, codec=codec)
        for codec in ("fp32", "int8")
    )
    devs, wire_ratios = [], []
    for _ in range(20):
        m_ref, m_lossy = ref.train_step(), lossy.train_step()
        devs.append(abs(m_ref.loss - m_lossy.loss))
        if m_lossy.miss_bytes.sum():
            wire_ratios.append(m_lossy.wire_bytes.sum()
                               / m_lossy.miss_bytes.sum())
    assert max(devs) < LOSS_TOL, max(devs)
    # the int8 store ships ~1/4 of the logical miss bytes every step
    assert wire_ratios and max(wire_ratios) < 0.3


# ---------------------------------------------------------------------------
# analytic twins: cost model and study rows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sync", ["halo", "ring"])
def test_cost_model_wire_bytes_fullbatch(wg, sync):
    from repro.core import cost_model
    from repro.core.partition_book import build_blockrow_book, build_edge_book

    g, *_ = wg
    if sync == "ring":
        book = build_blockrow_book(g, 4)
    else:
        book = build_edge_book(g, partition_edges(g, 4, "hep100", seed=1), 4)
    spec = _spec()
    base = cost_model.fullbatch_epoch(book, spec)
    fp32 = cost_model.fullbatch_epoch(book, spec, codec="fp32")
    int8 = cost_model.fullbatch_epoch(book, spec, codec="int8")
    # fp32/default: wire == logical, and the estimate is float-identical
    np.testing.assert_array_equal(base.wire_bytes, base.comm_bytes)
    np.testing.assert_array_equal(base.epoch_time, fp32.epoch_time)
    np.testing.assert_array_equal(base.comm_time, fp32.comm_time)
    # int8: quarter wire, cheaper comm, compute terms untouched
    np.testing.assert_allclose(int8.wire_bytes, 0.25 * int8.comm_bytes)
    assert (int8.comm_time <= base.comm_time + 1e-12).all()
    np.testing.assert_array_equal(int8.compute_time, base.compute_time)


def test_cost_model_wire_bytes_minibatch_and_serve():
    from repro.core import cost_model

    spec = _spec()
    args = (np.array([900.0]), np.array([400.0]), np.array([4000.0]),
            np.array([250.0]))
    base = cost_model.minibatch_step(*args, spec)
    int8 = cost_model.minibatch_step(*args, spec, codec="int8")
    np.testing.assert_array_equal(base.wire_bytes, base.fetch_bytes)
    np.testing.assert_allclose(int8.wire_bytes, 0.25 * base.fetch_bytes)
    assert (int8.fetch_time < base.fetch_time).all()
    assert int8.allreduce_time < base.allreduce_time

    sb = cost_model.serve_request(64, 40, 25, 300, spec, embed_dim=8, hops=1)
    s8 = cost_model.serve_request(64, 40, 25, 300, spec, embed_dim=8, hops=1,
                                  codec="int8")
    assert sb.wire_bytes == sb.fetch_bytes
    assert s8.wire_bytes == int(round(0.25 * sb.fetch_bytes))
    assert s8.service_time < sb.service_time


def test_study_rows_carry_codec_and_wire_columns():
    from repro.core.study import fullbatch_row

    kw = dict(scale=0.01, seed=0)
    base = fullbatch_row("OR", "hep100", 4, _spec(), **kw)
    int8 = fullbatch_row("OR", "hep100", 4, _spec(), codec="int8", **kw)
    assert base["codec"] == "fp32" and int8["codec"] == "int8"
    assert base["wire_bytes"] == base["comm_bytes"]
    assert int8["wire_bytes"] == pytest.approx(0.25 * int8["comm_bytes"])
    assert int8["epoch_time"] < base["epoch_time"]
