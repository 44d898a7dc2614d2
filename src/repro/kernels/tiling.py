"""Host-side tiled-edge layout for the segment-SpMM kernel (pure NumPy).

Kept jax-free on purpose: the partition books and the mini-batch sampler run
in the host/preprocessing layer (core/, gnn/sampling.py), which must not pay
the jax import just to sort edge lists. The device-side wrappers
(kernels/ops.py) re-export everything here.
"""

from __future__ import annotations

import numpy as np

DEFAULT_BLOCK_E = 512
DEFAULT_TILE_V = 256
DEFAULT_TILE_F = 128


def tiled_shape(num_rows: int, tile_v: int = DEFAULT_TILE_V) -> tuple[int, int]:
    """(rows_padded, n_tiles) of a tiled layout over `num_rows` rows — the
    ONE place this padding rule lives; every consumer (layout pass, kernel
    wrapper, partition book, sample plan) derives shapes from here."""
    rows_padded = int(np.ceil(max(num_rows, 1) / tile_v) * tile_v)
    return rows_padded, rows_padded // tile_v


def _check_dst_range(vdst: np.ndarray, num_rows: int, rows_padded: int) -> None:
    """Every (valid) destination must land inside the padded row range —
    edges past it would fall into row tiles the kernel grid never visits and
    silently vanish from the aggregate."""
    if vdst.size == 0:
        return
    lo, hi = int(vdst.min()), int(vdst.max())
    if lo < 0 or hi >= rows_padded:
        raise ValueError(
            f"tiled layout: dst out of range [0, {rows_padded}) "
            f"(num_rows={num_rows} padded to {rows_padded}); got "
            f"min={lo}, max={hi}. Edges aimed past the padded row range "
            f"would be silently dropped — mask them out via `valid` or "
            f"route them to an in-range padding sink row."
        )




def _tile_blocks(
    vdst: np.ndarray,
    num_rows: int,
    tile_v: int,
    block_e: int,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(row tile of each edge, edges of each row tile, edge blocks each row
    tile needs) for the valid destinations `vdst`: a tile needs its edge
    count rounded up to whole blocks, and at least one, so that the kernel
    writes every row tile."""
    rows_padded, n_tiles = tiled_shape(num_rows, tile_v)
    _check_dst_range(vdst, num_rows, rows_padded)
    tile_of = vdst // tile_v
    counts = np.bincount(tile_of, minlength=n_tiles)
    return tile_of, counts, np.maximum(-(-counts // block_e), 1)


def _valid_dst(dst: np.ndarray, valid: np.ndarray | None) -> np.ndarray:
    return np.asarray(dst if valid is None else dst[valid], dtype=np.int64)


def tiled_need_per_tile(
    dst: np.ndarray,
    num_rows: int,
    *,
    tile_v: int = DEFAULT_TILE_V,
    block_e: int = DEFAULT_BLOCK_E,
    valid: np.ndarray | None = None,
) -> int:
    """Smallest legal forced `per_tile` for this edge list — the block-rounded
    max per-tile edge count — without building the layout (O(E) bincount)."""
    blocks = _tile_blocks(_valid_dst(dst, valid), num_rows, tile_v, block_e)[2]
    return int(blocks.max()) * block_e


def tiled_need_blocks(
    dst: np.ndarray,
    num_rows: int,
    *,
    tile_v: int = DEFAULT_TILE_V,
    block_e: int = DEFAULT_BLOCK_E,
    valid: np.ndarray | None = None,
) -> int:
    """Edge blocks of this edge list's ragged layout — the smallest legal
    `n_blocks` — without building the layout (O(E) bincount)."""
    blocks = _tile_blocks(_valid_dst(dst, valid), num_rows, tile_v, block_e)[2]
    return int(blocks.sum())


def prepare_tiled_edges(
    dst: np.ndarray,
    num_rows: int,
    *,
    tile_v: int = DEFAULT_TILE_V,
    block_e: int = DEFAULT_BLOCK_E,
    per_tile: int | None = None,
    n_blocks: int | None = None,
    valid: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side layout pass (once per graph/partition): sort edges by row
    tile and give each tile the edge blocks it needs.

    Row tile t gets max(ceil(count_t / block_e), 1) blocks of block_e edges,
    so a tile is padded to its own edge count, not to the busiest tile's: on
    a graph whose degrees are skewed across row tiles the layout stays within
    one block a tile of the real messages. Blocks lie in tile order, and
    edges keep their input order within a tile. Every tile gets at least one
    block, so the kernel writes every row tile: rows no edge reaches come
    back as the combiner's identity.

    Returns (edge_order, local_dst, block_tile, rows_padded):
      edge_order [E_tiled]  — gather indices into the original edge list
                              (padding -> E, caller appends an identity row)
      local_dst  [E_tiled]  — row id within the edge's tile (padding -> tile_v)
      block_tile [n_blocks] — int32 row tile of each edge block,
                              non-decreasing; E_tiled = n_blocks * block_e

    `valid` (bool[E]) drops edges from the layout entirely; only edges whose
    messages carry the combiner identity (zero for sum, <= any real score for
    max) may be dropped (the aggregate stays exact). `per_tile` forces every
    tile's padded edge count instead (then block_tile[b] = b // (per_tile //
    block_e)); it must be a multiple of block_e and at least the largest
    per-tile edge count (`tiled_need_per_tile`). `n_blocks` forces the total
    block count, so several partitions / chunks share one static device
    shape; it must be at least what the tiles need (`tiled_need_blocks`).
    The blocks past that need are trailing pad blocks: block_tile =
    n_tiles - 1 and local_dst = tile_v, so they reach no row.

    Every valid dst must lie in [0, rows_padded) — anything past the padded
    row range raises ValueError rather than silently vanishing from the
    aggregate (its row tile would sit outside the kernel grid).
    """
    e = dst.shape[0]
    idx = np.arange(e) if valid is None else np.flatnonzero(valid)
    vdst = np.asarray(dst, dtype=np.int64)[idx]
    tile_of, counts, blocks = _tile_blocks(vdst, num_rows, tile_v, block_e)
    n_tiles = counts.shape[0]
    if per_tile is not None:
        need = int(blocks.max()) * block_e
        assert per_tile % block_e == 0 and per_tile >= need, (per_tile, need)
        blocks = np.full(n_tiles, per_tile // block_e)
    used = int(blocks.sum())
    if n_blocks is None:
        n_blocks = used
    assert n_blocks >= used, (n_blocks, used)
    block_tile = np.full(n_blocks, n_tiles - 1, dtype=np.int32)
    block_tile[:used] = np.repeat(np.arange(n_tiles, dtype=np.int32), blocks)

    order = np.argsort(tile_of, kind="stable")
    tile_sorted = tile_of[order]
    # slot of the i-th sorted edge: its tile's first slot + its rank there
    first_slot = (np.cumsum(blocks) - blocks) * block_e
    first_edge = np.cumsum(counts) - counts
    slot = (first_slot[tile_sorted] + np.arange(order.size)
            - first_edge[tile_sorted])
    edge_order = np.full(n_blocks * block_e, e, dtype=np.int64)
    local_dst = np.full(n_blocks * block_e, tile_v, dtype=np.int32)
    edge_order[slot] = idx[order]
    local_dst[slot] = vdst[order] - tile_sorted * tile_v
    rows_padded = n_tiles * tile_v
    return edge_order, local_dst, block_tile, rows_padded
