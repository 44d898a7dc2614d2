"""Pallas TPU kernel: tiled segment-reduce (sum | max) for GNN aggregation.

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
