"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,v,f", [(257, 256, 128), (1024, 512, 256),
                                   (50, 256, 128), (2000, 768, 128)])
def test_segment_spmm_sweep(e, v, f, dtype):
    rng = np.random.default_rng(e + v + f)
    dst = rng.integers(0, v, e).astype(np.int32)
    msgs = rng.normal(size=(e, f)).astype(np.float32)
    order, local_dst, btile, rows_p = ops.prepare_tiled_edges(dst, v)
    msgs_pad = np.concatenate([msgs, np.zeros((1, f), np.float32)])[order]
    out = ops.segment_spmm(
        jnp.asarray(msgs_pad, dtype), jnp.asarray(local_dst), rows_p,
        block_tile=jnp.asarray(btile), interpret=True,
    )
    expect = ref.segment_sum_ref(jnp.asarray(msgs, dtype), jnp.asarray(dst), v)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out[:v], np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol * 8,
    )


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,v,f", [(257, 256, 128), (1024, 512, 256),
                                   (50, 256, 4), (2000, 768, 128)])
def test_segment_reduce_max_sweep(e, v, f, dtype):
    """combiner="max" through the Pallas kernel (interpret) == the scatter
    `at[].max` oracle; rows with no edges are -inf under both. f=4 covers
    the GAT attention-score width (lane-padded tile)."""
    rng = np.random.default_rng(e + v + f)
    dst = rng.integers(0, v, e).astype(np.int32)
    msgs = rng.normal(size=(e, f)).astype(np.float32)
    order, local_dst, btile, rows_p = ops.prepare_tiled_edges(dst, v)
    msgs_pad = np.concatenate([msgs, np.full((1, f), -np.inf, np.float32)])[order]
    expect = ref.segment_max_ref(jnp.asarray(msgs, dtype), jnp.asarray(dst), v)
    tol = 1e-6 if dtype == np.float32 else 2e-2
    for kw in ({"use_pallas": False}, {"interpret": True}):
        out = ops.segment_spmm(
            jnp.asarray(msgs_pad, dtype), jnp.asarray(local_dst), rows_p,
            block_tile=jnp.asarray(btile), combiner="max", **kw)
        np.testing.assert_allclose(
            np.asarray(out[:v], np.float32), np.asarray(expect, np.float32),
            rtol=tol, atol=tol * 8,
        )


@pytest.mark.parametrize("combiner", ["sum", "max"])
def test_segment_reduce_paper_width_multi_block(combiner):
    """F=512 (4 feature tiles) with 3 edge blocks per row tile: each output
    block is revisited across the reduction axis and must keep the partial
    result of every earlier edge block."""
    rng = np.random.default_rng(11)
    v, f = 512, 512
    e = 2 * 3 * ops.DEFAULT_BLOCK_E - 100   # 2 row tiles, 3 blocks each
    dst = rng.integers(0, v, e).astype(np.int32)
    msgs = rng.normal(size=(e, f)).astype(np.float32)
    order, local_dst, btile, rows_p = ops.prepare_tiled_edges(dst, v)
    assert np.bincount(btile).min() >= 2
    assert f // ops._pick_tile_f(f) == 4
    fill = 0.0 if combiner == "sum" else -np.inf
    msgs_pad = np.concatenate([msgs, np.full((1, f), fill, np.float32)])[order]
    ref_fn = ref.segment_sum_ref if combiner == "sum" else ref.segment_max_ref
    expect = ref_fn(jnp.asarray(msgs), jnp.asarray(dst), v)
    out = ops.segment_spmm(jnp.asarray(msgs_pad), jnp.asarray(local_dst),
                           rows_p, block_tile=jnp.asarray(btile),
                           combiner=combiner, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:v]), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("combiner", ["sum", "max"])
def test_segment_spmm_oracle_unpadded_num_rows(combiner):
    """Regression: the oracle path derived n_tiles by floor division and
    assumed divisibility, so a direct call with an UNPADDED num_rows
    silently mis-binned every edge of the trailing tiles. Both paths now
    derive the grid from tiled_shape and return [num_rows, F]."""
    rng = np.random.default_rng(5)
    e, v, f = 900, 300, 8  # 300 rows -> 2 tiles of 256; 300 // 256 == 1
    dst = rng.integers(0, v, e).astype(np.int32)
    msgs = rng.normal(size=(e, f)).astype(np.float32)
    order, local_dst, btile, _ = ops.prepare_tiled_edges(dst, v)
    fill = 0.0 if combiner == "sum" else -np.inf
    msgs_pad = np.concatenate([msgs, np.full((1, f), fill, np.float32)])[order]
    ref_fn = ref.segment_sum_ref if combiner == "sum" else ref.segment_max_ref
    expect = ref_fn(jnp.asarray(msgs), jnp.asarray(dst), v)
    for kw in ({"use_pallas": False}, {"interpret": True}):
        out = ops.segment_spmm(
            jnp.asarray(msgs_pad), jnp.asarray(local_dst), v,  # unpadded!
            block_tile=jnp.asarray(btile), combiner=combiner, **kw)
        assert out.shape == (v, f)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)


def test_segment_spmm_layout_mismatch_fails_loudly():
    """An edge count that is not the blocks `block_tile` names, or too few
    blocks for the tile grid (layout built for a different num_rows/tile_v),
    must assert, not mis-bin silently."""
    msgs = jnp.zeros((3, 8), jnp.float32)  # 3 edges, not one block of 512
    local_dst = jnp.zeros((3,), jnp.int32)
    with pytest.raises(AssertionError, match="tiled layout mismatch"):
        ops.segment_spmm(msgs, local_dst, 300, use_pallas=False,
                         block_tile=jnp.zeros((1,), jnp.int32))
    msgs = jnp.zeros((ops.DEFAULT_BLOCK_E, 8), jnp.float32)  # 1 block, 2 tiles
    local_dst = jnp.zeros((ops.DEFAULT_BLOCK_E,), jnp.int32)
    with pytest.raises(AssertionError, match="tiled layout mismatch"):
        ops.segment_spmm(msgs, local_dst, 300, use_pallas=False,
                         block_tile=jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("fn", ["prepare_tiled_edges", "tiled_need_per_tile",
                                "tiled_need_blocks"])
def test_tiled_layout_rejects_out_of_range_dst(fn):
    """Regression: dst >= rows_padded grew the bincount past n_tiles and the
    trailing tiles' edges silently vanished from the aggregate. Both layout
    entry points now reject them; `valid`-masked bad edges stay allowed."""
    layout_fn = getattr(ops, fn)
    v = 100  # rows_padded = 256
    bad = np.array([0, 50, 600], np.int32)
    with pytest.raises(ValueError, match="dst out of range"):
        layout_fn(bad, v)
    with pytest.raises(ValueError, match="dst out of range"):
        layout_fn(np.array([-1, 3], np.int32), v)
    # masked out via `valid` -> accepted
    layout_fn(bad, v, valid=np.array([True, True, False]))
    # dst inside the padded range but past num_rows is an explicit padding
    # sink: allowed, lands in rows sliced off by the consumer
    layout_fn(np.array([0, 255], np.int32), v)


@pytest.mark.parametrize("tile_v,block_e", [(128, 256), (64, 128), (512, 512)])
def test_segment_spmm_nondefault_tiling(tile_v, block_e):
    """The oracle path must reconstruct global dst ids with the SAME tiling
    the layout was built with (regression: it hardcoded DEFAULT_TILE_V)."""
    rng = np.random.default_rng(tile_v + block_e)
    e, v, f = 900, 700, 32
    dst = rng.integers(0, v, e).astype(np.int32)
    msgs = rng.normal(size=(e, f)).astype(np.float32)
    order, local_dst, btile, rows_p = ops.prepare_tiled_edges(
        dst, v, tile_v=tile_v, block_e=block_e)
    msgs_pad = np.concatenate([msgs, np.zeros((1, f), np.float32)])[order]
    expect = ref.segment_sum_ref(jnp.asarray(msgs), jnp.asarray(dst), v)
    for kw in ({"use_pallas": False}, {"interpret": True}):
        out = ops.segment_spmm(
            jnp.asarray(msgs_pad), jnp.asarray(local_dst), rows_p,
            block_tile=jnp.asarray(btile), tile_v=tile_v, block_e=block_e,
            **kw)
        np.testing.assert_allclose(np.asarray(out[:v]), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["empty_tiles", "ragged_e", "tiny_rows"])
def test_prepare_tiled_edges_ragged(case):
    """Layout pass corner cases: row tiles with no edges, edge counts that
    don't divide block_e, and fewer rows than one tile."""
    rng = np.random.default_rng(0)
    f = 16
    if case == "empty_tiles":
        v, e = 1024, 300
        dst = rng.integers(0, 128, e).astype(np.int32)  # tiles 1..3 empty
    elif case == "ragged_e":
        v, e = 512, 515  # not a multiple of any block size
        dst = rng.integers(0, v, e).astype(np.int32)
    else:
        v, e = 7, 40  # num_rows < tile_v
        dst = rng.integers(0, v, e).astype(np.int32)
    msgs = rng.normal(size=(e, f)).astype(np.float32)
    order, local_dst, btile, rows_p = ops.prepare_tiled_edges(dst, v)
    assert rows_p % ops.DEFAULT_TILE_V == 0 and rows_p >= v
    assert order.shape == local_dst.shape
    assert order.shape[0] == btile.shape[0] * ops.DEFAULT_BLOCK_E
    assert (local_dst <= ops.DEFAULT_TILE_V).all()
    msgs_pad = np.concatenate([msgs, np.zeros((1, f), np.float32)])[order]
    expect = ref.segment_sum_ref(jnp.asarray(msgs), jnp.asarray(dst), v)
    for kw in ({"use_pallas": False}, {"interpret": True}):
        out = ops.segment_spmm(
            jnp.asarray(msgs_pad), jnp.asarray(local_dst), rows_p,
            block_tile=jnp.asarray(btile), **kw)
        np.testing.assert_allclose(np.asarray(out[:v]), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)


def test_prepare_tiled_edges_valid_mask_and_per_tile():
    """`valid` drops (zero-message) edges from the layout; `per_tile` forces
    a shared static shape."""
    rng = np.random.default_rng(3)
    v, e = 300, 400
    dst = rng.integers(0, v, e).astype(np.int32)
    valid = rng.random(e) < 0.5
    order, local_dst, btile, rows_p = ops.prepare_tiled_edges(
        dst, v, per_tile=1024, valid=valid)
    n_tiles = rows_p // ops.DEFAULT_TILE_V
    assert order.shape[0] == n_tiles * 1024
    kept = order[order < e]
    assert sorted(kept) == sorted(np.where(valid)[0])
    msgs = rng.normal(size=(e, 8)).astype(np.float32)
    msgs_pad = np.concatenate([msgs, np.zeros((1, 8), np.float32)])[order]
    out = ops.segment_spmm(
        jnp.asarray(msgs_pad), jnp.asarray(local_dst), rows_p,
        block_tile=jnp.asarray(btile), use_pallas=False)
    expect = ref.segment_sum_ref(
        jnp.asarray(msgs * valid[:, None]), jnp.asarray(dst), v)
    np.testing.assert_allclose(np.asarray(out[:v]), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def _skewed_dst(rng):
    """Edges over 4 row tiles of 256: a heavy tile 0 (3 blocks of 512), a
    tile 1 that no edge reaches, and two light tiles."""
    return rng.permutation(np.concatenate([
        rng.integers(0, 256, 1400), rng.integers(512, 768, 100),
        rng.integers(768, 1024, 300)])).astype(np.int32)


def _reduce_layout(msgs, dst, v, combiner, **kw):
    """Run `segment_spmm` (interpret) over a `prepare_tiled_edges` layout."""
    order, ldst, btile, rows_p = ops.prepare_tiled_edges(dst, v, **kw)
    fill = 0.0 if combiner == "sum" else -np.inf
    f = msgs.shape[1]
    msgs_pad = np.concatenate([msgs, np.full((1, f), fill, np.float32)])
    out = ops.segment_spmm(
        jnp.asarray(msgs_pad[order]), jnp.asarray(ldst), rows_p,
        block_tile=jnp.asarray(btile), combiner=combiner, interpret=True)
    return np.asarray(out[:v]), btile


@pytest.mark.parametrize("f", [4, 128, 512])
@pytest.mark.parametrize("combiner", ["sum", "max"])
def test_segment_reduce_skewed_ragged_layout(combiner, f):
    """The ragged grid on a skewed layout: one heavy tile whose output block
    folds three edge blocks, one tile no edge reaches (its rows come back as
    the combiner identity), and trailing pad blocks from a forced total."""
    rng = np.random.default_rng(f)
    v = 1024
    dst = _skewed_dst(rng)
    msgs = rng.normal(size=(dst.shape[0], f)).astype(np.float32)
    out, btile = _reduce_layout(msgs, dst, v, combiner, n_blocks=8)
    assert btile.tolist() == [0, 0, 0, 1, 2, 3, 3, 3]
    ref_fn = ref.segment_sum_ref if combiner == "sum" else ref.segment_max_ref
    expect = np.asarray(ref_fn(jnp.asarray(msgs), jnp.asarray(dst), v))
    assert (expect[256:512] == (0.0 if combiner == "sum" else -np.inf)).all()
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("f", [4, 128, 512])
@pytest.mark.parametrize("combiner", ["sum", "max"])
def test_segment_reduce_ragged_equals_uniform(combiner, f):
    """The ragged layout drops only padding blocks, and each tile keeps its
    edges in order and its block boundaries, so it reduces bit for bit
    like the uniform layout of the same graph."""
    rng = np.random.default_rng(f + 1)
    v = 1024
    dst = _skewed_dst(rng)
    msgs = rng.normal(size=(dst.shape[0], f)).astype(np.float32)
    ragged, r_tiles = _reduce_layout(msgs, dst, v, combiner)
    uniform, u_tiles = _reduce_layout(
        msgs, dst, v, combiner, per_tile=ops.tiled_need_per_tile(dst, v))
    assert r_tiles.shape[0] == 6 and u_tiles.shape[0] == 12
    np.testing.assert_array_equal(ragged, uniform)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,sq,skv,d", [
    (1, 2, 256, 256, 64),
    (2, 1, 512, 512, 128),
    (1, 2, 256, 1024, 64),   # cross-ish (longer kv)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, sq, skv, d, dtype, causal):
    if causal and sq != skv:
        pytest.skip("causal requires square for this contract")
    rng = np.random.default_rng(b * h + sq + d)
    q = jnp.asarray(rng.normal(size=(b, h, sq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, h, skv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, h, skv, d)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol * 5,
    )


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,s,d,valid", [
    (1, 2, 1024, 64, 700),
    (2, 4, 2048, 128, 2048),
    (1, 1, 1024, 64, 1),
])
def test_decode_attention_sweep(b, h, s, d, valid, dtype):
    rng = np.random.default_rng(s + d + valid)
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    out = ops.decode_attention(q, k, v, jnp.asarray(valid), interpret=True)
    expect = ref.decode_attention_ref(q, k, v, valid)
    tol = 2e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol * 5,
    )


def test_flash_custom_vjp_grads_match_reference():
    """The pure-JAX flash path (models.layers.attention) must produce the
    same gradients as direct-softmax autodiff."""
    from repro.models import layers as L

    rng = np.random.default_rng(0)
    B, H, S, D = 1, 2, 2048, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)

    def loss_flash(q, k, v):
        return (L.attention(q, k, v, causal=True, block_q=256, block_k=512) ** 2).sum()

    def loss_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_ssd_chunked_matches_sequential():
    """SSD chunked scan == naive per-token recurrence."""
    from repro.models.layers import ssd_chunked, ssd_decode_step

    rng = np.random.default_rng(1)
    b, s, h, p, g, n = 2, 64, 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 1.5, size=(h,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)

    y_chunk, final = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)

    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y_t, state = ssd_decode_step(
            x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], state)
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(state),
                               rtol=2e-4, atol=2e-4)


def test_moe_matches_dense_reference():
    """Capacity-bucketed MoE == dense per-expert computation (no drops)."""
    from repro.models import layers as L

    rng = np.random.default_rng(0)
    B, S, d, E, f, k = 2, 16, 8, 4, 12, 2
    p = {"router": jnp.asarray(rng.normal(size=(d, E)), jnp.float32),
         "w1": jnp.asarray(rng.normal(size=(E, d, f)), jnp.float32) * 0.1,
         "w3": jnp.asarray(rng.normal(size=(E, d, f)), jnp.float32) * 0.1,
         "w2": jnp.asarray(rng.normal(size=(E, f, d)), jnp.float32) * 0.1}
    x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    out, aux = L.moe_ffn(p, x, top_k=k, capacity_factor=100.0)

    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    vals, idx = jax.lax.top_k(probs, k)
    vals = vals / vals.sum(-1, keepdims=True)
    expect = jnp.zeros_like(x)
    for e in range(E):
        ye = (jax.nn.silu(x @ p["w1"][e]) * (x @ p["w3"][e])) @ p["w2"][e]
        w = jnp.where(idx == e, vals, 0).sum(-1)
        expect = expect + ye * w[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) > 0
