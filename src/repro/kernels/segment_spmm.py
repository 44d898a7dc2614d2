"""Pallas TPU kernel: tiled segment-reduce (sum | max | softmax) for GNN
aggregation.

The paper's compute hot spot is sparse neighbor aggregation (SpMM over the
partition-local edge list). TPU adaptation of the insight (DESIGN.md §2):
data-dependent scatters are hostile to the MXU/VPU, but a scatter whose
segment ids are PRE-SORTED and PRE-TILED becomes a dense tile operation. The
host (partition book) sorts edges by destination once per graph and blocks
them so one edge block touches one row tile. Each row tile gets only the
blocks its own edges fill (at least one), so the layout is ragged, and a
scalar-prefetched map `block_tile[b]` names the row tile of edge block b:

  grid = (feature_tiles, edge_blocks)            # reduction last
  out block of step (ft, b) = (block_tile[b], ft)
  kernel: P[r, e] = one_hot(local_dst)          (VPU compare on iota)
  sum:    acc    += P @ messages                (MXU matmul)
  max:    acc     = max(acc, masked-max over edge chunks)   (VPU)

A tile's blocks are consecutive, so its output block stays resident while
they fold into it (as in a grouped matmul), and is initialised to the
combiner identity at the tile's first block.

The same one-hot layout serves both combiners; only the init value (0 vs
-inf) and the accumulation differ. Max has no matmul form (it is a reduction
over the tropical semiring, which the MXU does not implement), so the kernel
sweeps the edge block in chunks sized to a VMEM budget and takes a masked
`jnp.max` per chunk — still fully dense and data-independent.

VMEM per step = BLOCK_E x TILE_F messages + TILE_V x TILE_F accumulator +
TILE_V x BLOCK_E one-hot (+ TILE_V x CHUNK_E x TILE_F and a BLOCK_E x
TILE_F int32 dst column for the max sweep) — all tiled to multiples of
(8, 128) lanes.

The softmax combiner (`segment_softmax`, GAT's edge softmax) runs on the
same grid and layout. Its messages are the source rows z of the heads in a
feature tile; per edge block it scores every (row, edge) pair of the tile
in VMEM, `leaky_relu(a_src . z_src + s_dst[row], 0.2)` masked by the
one-hot, and folds the block into three resident accumulators, an online
softmax per row and head as in flash attention:

  m_new = max(m, masked row max of the block)
  P     = exp(masked scores - m_new)           [tile_v, block_e]
  num   = num * exp(m - m_new) + P @ z          (MXU)
  den   = den * exp(m - m_new) + row sums of P

The source scores come from z by one small matmul with the heads' `a_src`
rows, so nothing per edge but z is read. Each head's max and denominator
are kept repeated over its lanes, so the outputs share one block. Where
a gradient is taken, two more accumulators, num_pos and den_pos, sum the
same over the edges whose score is not negative: the destination scores'
gradient is a node-sized expression of the four sums (ops.py).

Its backward (`segment_softmax_bwd`, for an edge list that is its own
transpose) runs on the same grid and layout: an edge of row r with
neighbour n stands for the reverse edge r -> n, so the gradient with
respect to the source row r is a sum over the row tile's own edges.
Per head and block it rescores the (row, edge) pairs from the neighbours'
gathered [g_num | s_dst | m | g_den] rows and the row tile's z, and folds

  w     = exp(leaky_relu(s_src[r] + s_dst[n]) - m[n])
  part += w @ g_num                              (MXU)
  sums += row sums of w (g_num[n] . z[r] + g_den[n]) leaky_relu'

into two resident accumulators, with no scatter.

The jit'd wrapper (ops.py) validates shapes and falls back to the pure-jnp
oracle (ref.py) on non-TPU backends; interpret=True is used by the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (  # noqa: F401 (canonical tile constants)
    DEFAULT_BLOCK_E,
    DEFAULT_TILE_F,
    DEFAULT_TILE_V,
)

COMBINERS = ("sum", "max")

# the softmax combiner's running-max floor: a row no edge reaches keeps it
# (den 0, num 0); finite, so that rescaling by exp(m - m_new) never makes NaN
SOFTMAX_FLOOR = -1e30

# VMEM budget for the max sweep's [tile_v, chunk_e, tile_f] intermediate
_MAX_SWEEP_BYTES = 2 << 20


def _lanes(tile_f: int) -> int:
    """Lane width a [*, tile_f] f32 value occupies in VMEM (tile_f padded
    up to the 128-lane vreg width)."""
    return -(-tile_f // 128) * 128


def _max_chunk_e(block_e: int, tile_v: int, tile_f: int) -> int:
    """Largest chunk of the edge block whose masked-max intermediate
    [tile_v, chunk_e, tile_f] fits the VMEM budget (chunk divides block_e).
    The budget counts lane-padded bytes: a tile_f=4 score tile still
    occupies full 128-lane vregs."""
    chunk = block_e
    while (chunk > 8 and chunk % 2 == 0
           and tile_v * chunk * _lanes(tile_f) * 4 > _MAX_SWEEP_BYTES):
        chunk //= 2
    return chunk


def _segment_reduce_kernel(tile_ref, dst_ref, msg_ref, out_ref, *scratch,
                           block_e, tile_v, combiner, chunk_e):
    """One grid step: fold one edge block into its row tile.

    tile_ref: [n_blocks]       int32 (SMEM, scalar-prefetched) — row tile
                               of each edge block, non-decreasing
    dst_ref: [1, block_e]      int32 — LOCAL row ids within this row tile
                               (pad edges -> tile_v, i.e. out of range)
    msg_ref: [block_e, tile_f] message block
    out_ref: [tile_v, tile_f]  row-tile accumulator. The edge-block axis is
                               the innermost grid axis and a tile's blocks
                               are consecutive, so the block stays resident
                               across them; it is initialised at the tile's
                               first block to the combiner identity (0 for
                               sum, -inf for max)
    scratch: max only — [block_e, tile_f] int32 column copy of dst_ref
    """
    b = pl.program_id(1)

    @pl.when((b == 0) | (tile_ref[b] != tile_ref[jnp.maximum(b - 1, 0)]))
    def _init():
        if combiner == "sum":
            out_ref[...] = jnp.zeros_like(out_ref)
        else:
            out_ref[...] = jnp.full_like(out_ref, -jnp.inf)

    if combiner == "sum":
        # one-hot [tile_v, block_e] via iota comparison (VPU), then an MXU
        # matmul: out-of-range (padding) dst rows vanish in the one-hot
        rows = jax.lax.broadcasted_iota(jnp.int32, (tile_v, block_e), 0)
        hits = rows == dst_ref[...]
        # the MXU accumulates in f32 whatever the message dtype
        part = jax.lax.dot(hits.astype(msg_ref.dtype), msg_ref[...],
                           preferred_element_type=jnp.float32)
        out_ref[...] = (out_ref[...].astype(jnp.float32)
                        + part).astype(out_ref.dtype)
        return

    # masked max, swept in chunks so the [tile_v, chunk_e, tile_f]
    # broadcast stays within the VMEM budget; padding edges hit no row and
    # contribute -inf (the max identity). The sweep needs dst along
    # sublanes (one edge per row), so the lane-major dst block is
    # transposed once into scratch and both operands are sliced as refs.
    (dst_col_ref,) = scratch
    tile_f = out_ref.shape[1]
    lanes = _lanes(tile_f)
    dst_col_ref[...] = jnp.broadcast_to(
        dst_ref[...], (lanes, block_e)).T[:, :tile_f]
    neg_inf = jnp.asarray(-jnp.inf, out_ref.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile_v, chunk_e, tile_f), 0)

    def body(i, acc):
        start = pl.multiple_of(i * chunk_e, chunk_e)
        m = msg_ref[pl.ds(start, chunk_e), :].astype(out_ref.dtype)
        d = dst_col_ref[pl.ds(start, chunk_e), :]
        cand = jnp.max(jnp.where(rows == d[None], m[None], neg_inf), axis=1)
        return jnp.maximum(acc, cand)

    out_ref[...] = jax.lax.fori_loop(
        0, block_e // chunk_e, body, out_ref[...])


def segment_spmm(
    messages: jnp.ndarray,    # [E, F] edge messages, pre-sorted by dst tile
    local_dst: jnp.ndarray,   # [E] int32 row id WITHIN the edge's row tile
    block_tile: jnp.ndarray,  # [E / block_e] int32 row tile of each block
    num_rows: int,
    *,
    combiner: str = "sum",
    block_e: int = DEFAULT_BLOCK_E,
    tile_v: int = DEFAULT_TILE_V,
    tile_f: int = DEFAULT_TILE_F,
    interpret: bool = False,
) -> jnp.ndarray:
    """Segment reduce with the tiling contract described in the module
    docstring. `combiner` is static: "sum" (init 0, MXU one-hot matmul) or
    "max" (init -inf, VPU masked max). Rows no edge reaches come back as the
    combiner identity (0 / -inf).

    E must be cut into blocks of block_e edges, each within one row tile:
    block b holds edges of row tile block_tile[b], block_tile is
    non-decreasing and names every row tile at least once, and pad edges
    carry local_dst == tile_v (an out-of-range row hits nothing under either
    combiner). `prepare_tiled_edges` (tiling.py) produces this layout from
    raw (dst, msg).
    """
    assert combiner in COMBINERS, combiner
    e, f = messages.shape
    assert num_rows % tile_v == 0, (num_rows, tile_v)
    assert f % tile_f == 0, (f, tile_f)
    n_blocks = block_tile.shape[0]
    assert e == n_blocks * block_e, (e, n_blocks, block_e)

    # reduction (edge-block) axis innermost: the output block
    # (block_tile[b], ft) stays resident while a tile's blocks fold into it
    grid = (f // tile_f, n_blocks)
    kernel = functools.partial(
        _segment_reduce_kernel, block_e=block_e, tile_v=tile_v,
        combiner=combiner, chunk_e=_max_chunk_e(block_e, tile_v, tile_f),
    )
    scratch = ([pltpu.VMEM((block_e, tile_f), jnp.int32)]
               if combiner == "max" else [])
    # [E] -> [E / block_e, 1, block_e]: a 1-D int32 block does not match
    # the TPU's HBM tiling; a (1, block_e) trailing block does
    dst_blocks = local_dst.reshape(n_blocks, 1, block_e)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, block_e), lambda ft, b, tiles: (b, 0, 0)),
            pl.BlockSpec((block_e, tile_f), lambda ft, b, tiles: (b, ft)),
        ],
        out_specs=pl.BlockSpec((tile_v, tile_f),
                               lambda ft, b, tiles: (tiles[b], ft)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_rows, f), messages.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=f"segment_spmm_{combiner}",
    )(block_tile.astype(jnp.int32), dst_blocks, messages)


def _mxu(a, b):
    """a @ b on the MXU at the default precision (bfloat16 operands on a
    TPU), accumulated in float32."""
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _segment_softmax_kernel(tile_ref, dst_ref, z_ref, a_ref, sdst_ref,
                            num_ref, den_ref, m_ref, *pos_refs,
                            block_e, tile_v, head_dim):
    """One grid step of the softmax combiner: fold one edge block into its
    row tile's accumulators.

    z_ref:    [block_e, tile_f] source rows of the block's edges
    a_ref:    [rows, tile_f]    row h holds head h's `a_src` in its lanes
    sdst_ref: [tile_v, tile_f]  destination scores, repeated over each
                                head's lanes
    num_ref, den_ref, m_ref: [tile_v, tile_f] resident accumulators; den
                                and m repeated over each head's lanes
    pos_refs: none, or (num_pos, den_pos) [tile_v, tile_f]: num and den
                                over the edges whose score is not negative
                                (the backward's leaky_relu' is 1 there),
                                rescaled alike
    """
    b = pl.program_id(1)

    @pl.when((b == 0) | (tile_ref[b] != tile_ref[jnp.maximum(b - 1, 0)]))
    def _init():
        for ref in (num_ref, den_ref, *pos_refs):
            ref[...] = jnp.zeros_like(ref)
        m_ref[...] = jnp.full_like(m_ref, SOFTMAX_FLOOR)

    tile_f = z_ref.shape[1]
    heads = tile_f // head_dim
    z = z_ref[...].astype(jnp.float32)
    hits = (jax.lax.broadcasted_iota(jnp.int32, (tile_v, block_e), 0)
            == dst_ref[...])
    # source scores of the block, one row per head: [rows, block_e]
    s_src = jax.lax.dot_general(
        a_ref[...], z, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    m_old, s_dst = m_ref[...], sdst_ref[...]
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (tile_v, tile_f),
                                         1) // head_dim
    z_head = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) // head_dim
    # per head: [tile_v, 1] columns (rescale, new max, weight sums), and
    # the block's [tile_v, tile_f] products, all heads' lanes together
    cols = {}
    part = jnp.zeros(num_ref.shape, jnp.float32)
    part_pos = jnp.zeros(num_ref.shape, jnp.float32)
    for h in range(heads):
        if heads == 1:
            col = lambda t: t[:, :1]  # noqa: E731 (every lane holds it)
            zh = z
        else:
            col = lambda t: jnp.max(  # noqa: E731 (head h's lanes)
                jnp.where(lane_head == h, t, SOFTMAX_FLOOR), axis=1,
                keepdims=True)
            zh = jnp.where(z_head == h, z, 0.0)
        pre = s_src[h:h + 1, :] + col(s_dst)
        s = jnp.where(hits, jnp.maximum(pre, 0.2 * pre), -jnp.inf)
        m_h = col(m_old)
        mn_h = jnp.maximum(m_h, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - mn_h)                    # 0 where no edge hits
        part = part + _mxu(p, zh)
        got = {"scale": jnp.exp(m_h - mn_h), "m": mn_h,
               "den": jnp.sum(p, axis=1, keepdims=True)}
        if pos_refs:
            p_pos = jnp.where(pre >= 0, p, 0.0)
            part_pos = part_pos + _mxu(p_pos, zh)
            got["dpos"] = jnp.sum(p_pos, axis=1, keepdims=True)
        for k, v in got.items():
            cols[k] = v if heads == 1 else jnp.where(
                lane_head == h, v, cols.get(k, 0.0))
    scale = cols["scale"]
    num_ref[...] = num_ref[...] * scale + part
    den_ref[...] = den_ref[...] * scale + cols["den"]
    m_ref[...] = jnp.broadcast_to(cols["m"], m_ref.shape)
    if pos_refs:
        npos_ref, dpos_ref = pos_refs
        npos_ref[...] = npos_ref[...] * scale + part_pos
        dpos_ref[...] = dpos_ref[...] * scale + cols["dpos"]


def segment_softmax(
    z: jnp.ndarray,           # [E, W] source rows, pre-sorted by dst tile
    local_dst: jnp.ndarray,   # [E] int32 row id WITHIN the edge's row tile
    block_tile: jnp.ndarray,  # [E / block_e] int32 row tile of each block
    a_rows: jnp.ndarray,      # [W / tile_f, rows, tile_f] heads' a_src
    s_dst: jnp.ndarray,       # [num_rows, W] dst scores over head lanes
    num_rows: int,
    *,
    head_dim: int,
    block_e: int = DEFAULT_BLOCK_E,
    tile_v: int = DEFAULT_TILE_V,
    tile_f: int = DEFAULT_TILE_F,
    with_pos: bool = False,
    interpret: bool = False,
) -> tuple:
    """The softmax combiner on the layout `segment_spmm` takes. Returns
    (num, den, m), each [num_rows, W], den and m repeated over each head's
    `head_dim` lanes; `with_pos` (static) adds (num_pos, den_pos), num and
    den over the edges whose score is not negative, which the backward's
    destination scores need. A feature tile holds whole heads (`tile_f` is
    a multiple of `head_dim`), and `a_rows[ft]` holds in row h the `a_src`
    of the tile's h-th head, in that head's lanes. Rows no edge reaches
    come back as (0, 0, SOFTMAX_FLOOR), and 0 for the two sums."""
    e, w = z.shape
    assert num_rows % tile_v == 0, (num_rows, tile_v)
    assert w % tile_f == 0 and tile_f % head_dim == 0, (w, tile_f, head_dim)
    n_blocks = block_tile.shape[0]
    assert e == n_blocks * block_e, (e, n_blocks, block_e)
    rows = a_rows.shape[1]
    outputs = 5 if with_pos else 3
    kernel = functools.partial(_segment_softmax_kernel, block_e=block_e,
                               tile_v=tile_v, head_dim=head_dim)
    dst_blocks = local_dst.reshape(n_blocks, 1, block_e)
    row_spec = pl.BlockSpec((tile_v, tile_f),
                            lambda ft, b, tiles: (tiles[b], ft))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w // tile_f, n_blocks),
        in_specs=[
            pl.BlockSpec((None, 1, block_e), lambda ft, b, tiles: (b, 0, 0)),
            pl.BlockSpec((block_e, tile_f), lambda ft, b, tiles: (b, ft)),
            pl.BlockSpec((None, rows, tile_f),
                         lambda ft, b, tiles: (ft, 0, 0)),
            row_spec,
        ],
        out_specs=[row_spec] * outputs,
    )
    out = jax.ShapeDtypeStruct((num_rows, w), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out] * outputs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="segment_spmm_softmax",
    )(block_tile.astype(jnp.int32), dst_blocks, z, a_rows, s_dst)


def _segment_softmax_bwd_kernel(tile_ref, dst_ref, g_ref, t_ref, sel_ref,
                                z_ref, ssrc_ref, part_ref, sums_ref, *,
                                block_e, tile_v, head_dim):
    """One grid step of the reverse pass: the layout's edges of a row tile,
    read as the reverse edges out of its rows, folded into the row tile.

    An edge of row r with neighbour n stands for the edge r -> n, whose
    weight is w = exp(leaky_relu(s_src[r] + s_dst[n]) - m[n]).

    g_ref:    [block_e, >= tile_f] the neighbours' rows; lanes [0, tile_f)
                                hold g_num of the tile's heads
    t_ref:    [block_e, tail]   the neighbours' [s_dst | m | g_den] lanes
    sel_ref:  [rows, tail]      row k*heads + h picks quantity k (s_dst,
                                m, g_den) of the tile's head h
    z_ref, ssrc_ref: [tile_v, tile_f] the row tile's z and its source
                                scores, repeated over each head's lanes
    part_ref: [tile_v, tile_f]  resident: sum of w g_num[n]
    sums_ref: [tile_v, tile_f]  resident: sum of
                                ds = w (g_num[n] . z[r] + g_den[n]) leaky_relu',
                                repeated over each head's lanes
    """
    b = pl.program_id(1)

    @pl.when((b == 0) | (tile_ref[b] != tile_ref[jnp.maximum(b - 1, 0)]))
    def _init():
        part_ref[...] = jnp.zeros_like(part_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    tile_f = z_ref.shape[1]
    heads = tile_f // head_dim
    z = z_ref[...]
    g = g_ref[...][:, :tile_f]
    hits = (jax.lax.broadcasted_iota(jnp.int32, (tile_v, block_e), 0)
            == dst_ref[...])
    # the neighbours' per-head values, one row per (quantity, head):
    # [rows, block_e], exact (a 0/1 selection at full precision)
    per_edge = jax.lax.dot_general(
        sel_ref[...], t_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    s_src = ssrc_ref[...]
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (tile_v, tile_f),
                                         1) // head_dim
    g_head = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1) // head_dim
    part = jnp.zeros(part_ref.shape, jnp.float32)
    sums = None
    for h in range(heads):
        if heads == 1:
            col = lambda t: t[:, :1]  # noqa: E731 (every lane holds it)
            zh, gh = z, g
        else:
            col = lambda t: jnp.max(  # noqa: E731 (head h's lanes)
                jnp.where(lane_head == h, t, SOFTMAX_FLOOR), axis=1,
                keepdims=True)
            zh = jnp.where(lane_head == h, z, 0.0)
            gh = jnp.where(g_head == h, g, 0.0)
        s_dst, m, g_den = (per_edge[k * heads + h:k * heads + h + 1, :]
                           for k in range(3))
        pre = col(s_src) + s_dst
        w = jnp.exp(jnp.where(hits, jnp.maximum(pre, 0.2 * pre) - m,
                              -jnp.inf))            # 0 where no edge hits
        # g_num[n] . z[r] of head h for every (row, edge) pair
        gz = jax.lax.dot_general(
            zh, g, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        ds = w * (gz + g_den) * jnp.where(pre >= 0, 1.0, 0.2)
        part = part + _mxu(w, gh)
        d_h = jnp.sum(ds, axis=1, keepdims=True)
        sums = d_h if heads == 1 else jnp.where(
            lane_head == h, d_h, 0.0 if sums is None else sums)
    part_ref[...] += part
    sums_ref[...] += jnp.broadcast_to(sums, sums_ref.shape)


def segment_softmax_bwd(
    rows: jnp.ndarray,        # [E, T] neighbour rows, pre-sorted by dst tile
    local_dst: jnp.ndarray,   # [E] int32 row id WITHIN the edge's row tile
    block_tile: jnp.ndarray,  # [E / block_e] int32 row tile of each block
    select: jnp.ndarray,      # [W / tile_f, rows, tail] per-tile selector
    z: jnp.ndarray,           # [num_rows, W] the rows' z
    s_src: jnp.ndarray,       # [num_rows, W] source scores over head lanes
    num_rows: int,
    *,
    head_dim: int,
    tail_block: int,
    block_e: int = DEFAULT_BLOCK_E,
    tile_v: int = DEFAULT_TILE_V,
    tile_f: int = DEFAULT_TILE_F,
    interpret: bool = False,
) -> tuple:
    """The softmax combiner's backward in one pass over the forward's own
    layout, for an edge list that is its own transpose: each row tile's
    edges are the reverse edges out of its rows, so both sums of the
    gradient with respect to a source row fold into resident row tiles,
    with no scatter. `rows[e]` is the layout edge's neighbour's
    [g_num | s_dst | m | g_den] (g_num in lanes [0, W)); its tail is the
    `tail`-lane block number `tail_block`, the block [0, T) itself where one
    feature tile spans the rows (T = tail). Returns (part, sums), each
    [num_rows, W]: the sum over a row's reverse edges of w g_num and of
    ds, the latter repeated over each head's lanes."""
    e, t_w = rows.shape
    w = z.shape[1]
    assert num_rows % tile_v == 0, (num_rows, tile_v)
    assert w % tile_f == 0 and tile_f % head_dim == 0, (w, tile_f, head_dim)
    n_blocks = block_tile.shape[0]
    assert e == n_blocks * block_e, (e, n_blocks, block_e)
    sel_rows, tail = select.shape[1:]
    kernel = functools.partial(_segment_softmax_bwd_kernel, block_e=block_e,
                               tile_v=tile_v, head_dim=head_dim)
    dst_blocks = local_dst.reshape(n_blocks, 1, block_e)
    row_spec = pl.BlockSpec((tile_v, tile_f),
                            lambda ft, b, tiles: (tiles[b], ft))
    g_width = t_w if w == tile_f else tile_f
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(w // tile_f, n_blocks),
        in_specs=[
            pl.BlockSpec((None, 1, block_e), lambda ft, b, tiles: (b, 0, 0)),
            pl.BlockSpec((block_e, g_width), lambda ft, b, tiles: (b, ft)),
            pl.BlockSpec((block_e, tail),
                         lambda ft, b, tiles: (b, tail_block)),
            pl.BlockSpec((None, sel_rows, tail),
                         lambda ft, b, tiles: (ft, 0, 0)),
            row_spec,
            row_spec,
        ],
        out_specs=[row_spec] * 2,
    )
    out = jax.ShapeDtypeStruct((num_rows, w), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[out] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="segment_spmm_softmax_bwd",
    )(block_tile.astype(jnp.int32), dst_blocks, rows, rows, select, z, s_src)
