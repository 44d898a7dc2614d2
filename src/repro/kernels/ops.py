"""jit'd public wrappers for the Pallas kernels, with platform dispatch.

On TPU the Pallas kernels run natively; elsewhere (this CPU container) the
wrappers dispatch to the pure-jnp oracle so the rest of the system never
cares. `interpret=True` forces the kernel body through the Pallas
interpreter (tests validate kernels this way, per-shape/dtype, against the
oracles in ref.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.obs.trace import get_tracer
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.segment_spmm import SOFTMAX_FLOOR
from repro.kernels.segment_spmm import segment_softmax as _softmax_pallas
from repro.kernels.segment_spmm import (
    segment_softmax_bwd as _softmax_bwd_pallas,
)
from repro.kernels.segment_spmm import segment_spmm as _spmm_pallas
from repro.kernels.tiling import (  # noqa: F401 (re-exported host-layout API)
    DEFAULT_BLOCK_E,
    DEFAULT_TILE_F,
    DEFAULT_TILE_V,
    prepare_tiled_edges,
    tiled_need_blocks,
    tiled_need_per_tile,
    tiled_shape,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# segment spmm (GNN aggregation)
# ---------------------------------------------------------------------------


def _pick_tile_f(f: int) -> int:
    """Lane tiling: the MXU-friendly 128 when it divides f, else f itself
    (small feature dims; Pallas pads lanes internally)."""
    return DEFAULT_TILE_F if f % DEFAULT_TILE_F == 0 else f


@functools.partial(jax.jit, static_argnames=(
    "num_rows", "combiner", "tile_v", "block_e", "use_pallas", "interpret"))
def segment_spmm(
    messages: jnp.ndarray,
    local_dst: jnp.ndarray,
    num_rows: int,
    *,
    block_tile: jnp.ndarray,
    combiner: str = "sum",
    tile_v: int = DEFAULT_TILE_V,
    block_e: int = DEFAULT_BLOCK_E,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Tiled segment-reduce (`combiner` in {"sum", "max"}; see
    kernels/segment_spmm.py for the combiner semantics — init 0 vs -inf,
    MXU one-hot matmul vs VPU masked max). `messages`/`local_dst`/
    `block_tile` must come from a `prepare_tiled_edges` layout built with
    the SAME (tile_v, block_e); non-TPU backends use the oracle. `num_rows`
    may be unpadded — both paths derive the tile grid from `tiled_shape` and
    return [num_rows, F]."""
    # Derive the grid the layout was built with (the ONE padding rule,
    # tiling.tiled_shape) — floor-dividing num_rows here would mis-bin every
    # edge of the trailing tiles when num_rows is unpadded.
    e = messages.shape[0]
    rows_padded, n_tiles = tiled_shape(num_rows, tile_v)
    n_blocks = block_tile.shape[0]
    assert e == n_blocks * block_e and n_blocks >= n_tiles, (
        f"tiled layout mismatch: {e} edges are not the {n_blocks} blocks of "
        f"{block_e} that block_tile names, or too few blocks for {n_tiles} "
        f"row tiles (num_rows={num_rows}, tile_v={tile_v}); was the layout "
        f"built with a different (num_rows, tile_v, block_e)?")
    use = _on_tpu() if use_pallas is None else use_pallas
    if use or interpret:
        out = _spmm_pallas(
            messages, local_dst, block_tile, rows_padded,
            combiner=combiner, block_e=block_e, tile_v=tile_v,
            tile_f=_pick_tile_f(messages.shape[1]),
            interpret=interpret or not _on_tpu(),
        )
        return out[:num_rows]
    # oracle path: local_dst is tile-relative; rebuild global ids
    tile_idx = block_tile[jnp.arange(e) // block_e]
    gdst = jnp.where(
        local_dst >= tile_v, num_rows, tile_idx * tile_v + local_dst
    ).astype(jnp.int32)
    if combiner == "max":
        return ref.segment_max_ref(messages, gdst, num_rows)
    return ref.segment_sum_ref(messages, gdst, num_rows)


# ---------------------------------------------------------------------------
# aggregate — the GNN aggregation primitive (scatter | tiled | pallas)
# ---------------------------------------------------------------------------

AGG_BACKENDS = ("scatter", "tiled", "pallas")

# The data-dependent scatter primitives the no-scatter rule hunts for in
# traced programs (repro.analysis). Plain "scatter" (static-index
# `at[].set`, e.g. zeroing the dummy row) is deliberately NOT listed: its
# indices are compile-time constants, so it lowers to a cheap in-place
# update, not the O(E) data-dependent scatter the tiled backends exist to
# eliminate.
SCATTER_PRIMITIVES = ("scatter-add", "scatter-max")


def scatter_free_traced(backend: str) -> bool:
    """Whether `aggregate(backend=...)` traces WITHOUT data-dependent
    scatter primitives on this host.

    "pallas" always forces the kernel (interpreted off-TPU), so its trace
    is scatter-free everywhere. "tiled" lowers to the same kernel on TPU
    but falls back to the jnp scatter ORACLE off-TPU (numerics over speed
    on hosts with no tiled advantage) — so off-TPU its trace legitimately
    contains scatter-add/scatter-max. "scatter" is the oracle by
    definition. The analysis no-scatter rule derives each program's
    expectation from this single predicate.
    """
    if backend == "pallas":
        return True
    return backend == "tiled" and _on_tpu()


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _tiled_aggregate(num_rows, tile_v, block_e, use_pallas, interpret,
                     messages, dst, edge_order, local_dst, block_tile):
    """Tiled segment-sum of `messages` into `num_rows` rows.

    Forward runs the pre-sorted / pre-blocked layout (gather by `edge_order`,
    then the tiled kernel). Backward exploits that a segment-sum's transpose
    is a plain gather: grad_messages = g[dst] — cheap and Pallas-free.
    """
    del dst  # forward uses the tiled layout only; dst feeds the backward
    e, f = messages.shape
    with jax.named_scope("edge.reorder"):
        msg_pad = jnp.concatenate(
            [messages, jnp.zeros((1, f), messages.dtype)], axis=0)
        ordered = msg_pad[edge_order]
    with jax.named_scope("edge.reduce"):
        return segment_spmm(
            ordered, local_dst, num_rows, block_tile=block_tile,
            tile_v=tile_v, block_e=block_e,
            use_pallas=use_pallas, interpret=interpret,
        )


def _tiled_aggregate_fwd(num_rows, tile_v, block_e, use_pallas, interpret,
                         messages, dst, edge_order, local_dst, block_tile):
    out = _tiled_aggregate(num_rows, tile_v, block_e, use_pallas, interpret,
                           messages, dst, edge_order, local_dst, block_tile)
    return out, dst


@jax.named_scope("edge.reduce")
def _tiled_aggregate_bwd(num_rows, tile_v, block_e, use_pallas, interpret,
                         dst, g):
    # transpose of the pre-sorted scatter-add: a gather (pad dst -> zero row)
    g_pad = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)], axis=0)
    grad_messages = g_pad[jnp.minimum(dst, num_rows)]
    return grad_messages, None, None, None, None


_tiled_aggregate.defvjp(_tiled_aggregate_fwd, _tiled_aggregate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _tiled_aggregate_max(num_rows, tile_v, block_e, use_pallas, interpret,
                         messages, dst, edge_order, local_dst, block_tile):
    """Tiled segment-max of `messages` into `num_rows` rows.

    Forward runs the same pre-sorted / pre-blocked layout as the sum, with
    combiner="max" (init -inf). Backward is a masked-argmax gather: the
    cotangent of row r flows to the layout-present edges whose message
    EQUALS the row max (exact — the kernel takes maxes without arithmetic,
    so the comparison reproduces the forward selection), split evenly among
    ties — the same even-subgradient convention as the `at[].max` scatter
    oracle, so gradients match it on any data the layout kept in full; an
    edge the layout dropped (`valid`-masked) is not part of the computed
    max and gets zero cotangent even if its message ties the surviving row
    max (the scatter oracle, which still sees that edge, would hand it a
    tie share — gradient parity on dropped edges needs them strictly below
    the surviving max). No model calls it: GAT's softmax takes its row
    max inside `edge_softmax`.
    """
    del dst  # forward uses the tiled layout only; dst feeds the backward
    e, f = messages.shape
    with jax.named_scope("edge.reorder"):
        msg_pad = jnp.concatenate(
            [messages, jnp.full((1, f), -jnp.inf, messages.dtype)], axis=0)
        ordered = msg_pad[edge_order]
    with jax.named_scope("edge.reduce"):
        return segment_spmm(
            ordered, local_dst, num_rows, block_tile=block_tile,
            combiner="max", tile_v=tile_v, block_e=block_e,
            use_pallas=use_pallas, interpret=interpret,
        )


def _tiled_aggregate_max_fwd(num_rows, tile_v, block_e, use_pallas, interpret,
                             messages, dst, edge_order, local_dst, block_tile):
    out = _tiled_aggregate_max(num_rows, tile_v, block_e, use_pallas,
                               interpret, messages, dst, edge_order, local_dst,
                               block_tile)
    return out, (messages, dst, out, edge_order, local_dst, block_tile)


@jax.named_scope("edge.reduce")
def _tiled_aggregate_max_bwd(num_rows, tile_v, block_e, use_pallas, interpret,
                             res, g):
    messages, dst, out, edge_order, local_dst, block_tile = res
    e, f = messages.shape
    dstc = jnp.minimum(dst, num_rows)
    # The forward maxes over the edges PRESENT in the layout; a dropped
    # (`valid`-masked) edge is not part of the computed function even when
    # its message happens to tie the surviving row max, so it must get zero
    # cotangent (and stay out of the tie denominator — which the layouted
    # tie count below already guarantees).
    in_layout = jnp.zeros((e + 1,), jnp.bool_).at[edge_order].set(True)[:e]
    # sink row compares against +inf (never the max) and carries zero grad
    out_pad = jnp.concatenate(
        [out, jnp.full((1, f), jnp.inf, out.dtype)], axis=0)
    g_pad = jnp.concatenate([g, jnp.zeros((1, f), g.dtype)], axis=0)
    is_max = (messages == out_pad[dstc]) & in_layout[:, None]
    # even split among ties (the scatter oracle's subgradient convention):
    # per-row tie counts via the tiled segment-sum of the argmax mask
    ties = _tiled_aggregate(num_rows, tile_v, block_e, use_pallas, interpret,
                            is_max.astype(g.dtype), dst, edge_order, local_dst,
                            block_tile)
    ties_pad = jnp.concatenate([ties, jnp.ones((1, f), g.dtype)], axis=0)
    share = g_pad[dstc] / jnp.maximum(ties_pad[dstc], 1.0)
    grad_messages = jnp.where(is_max, share, 0.0).astype(messages.dtype)
    return grad_messages, None, None, None, None


_tiled_aggregate_max.defvjp(_tiled_aggregate_max_fwd, _tiled_aggregate_max_bwd)


AGG_REDUCES = ("sum", "max")


def aggregate(
    messages: jnp.ndarray,    # [E, F] per-edge messages (original edge order)
    dst: jnp.ndarray,         # [E] int32 destination row per edge (< num_rows)
    num_rows: int,
    *,
    edge_order: jnp.ndarray | None = None,  # from prepare_tiled_edges
    local_dst: jnp.ndarray | None = None,
    block_tile: jnp.ndarray | None = None,
    backend: str = "scatter",
    reduce: str = "sum",
    tile_v: int = DEFAULT_TILE_V,
    block_e: int = DEFAULT_BLOCK_E,
    interpret: bool = False,
) -> jnp.ndarray:
    """Segment-reduce `messages` into `[num_rows, F]` vertex rows.

    backend:
      scatter — data-dependent `at[].add` / `at[].max` (the oracle; XLA
                scatter)
      tiled   — `prepare_tiled_edges` layout (edge_order, local_dst,
                block_tile) through the tiled segment-reduce (jnp oracle
                off-TPU, Pallas kernel on TPU); custom_vjp backward
      pallas  — like tiled but forces the Pallas kernel (interpreted on CPU;
                tests use this)

    reduce:
      sum — the segment-SpMM. Backward is a plain gather g[dst] (the
            transpose of a pre-sorted scatter-add).
      max — segment-max (init -inf: rows no edge reaches come back as -inf;
            clamp with jnp.maximum against a finite floor before exp/log).
            Backward is a masked-argmax gather, split evenly among tied
            edges (the scatter oracle's convention, so gradients match it
            even on ties). GAT's softmax does not use it: `edge_softmax`
            takes the row max in its own single pass.

    The tiled layout may drop `valid`-masked edges — forward values still
    match the scatter oracle as long as dropped messages carry the reduce
    identity's certainty: identically zero for sum, at or below every
    surviving score for max. Gradients match too, except that a dropped
    edge exactly TYING the surviving row max gets zero cotangent here (it is not part of
    the computed max) while the scatter oracle — which still sees it —
    hands it a tie share; strict inequality on dropped edges restores full
    parity.
    """
    if reduce not in AGG_REDUCES:
        raise ValueError(f"unknown aggregate reduce {reduce!r}; "
                         f"options: {AGG_REDUCES}")
    if backend == "scatter":
        with jax.named_scope("edge.reduce"):
            if reduce == "max":
                out = jnp.full((num_rows + 1, messages.shape[-1]), -jnp.inf,
                               messages.dtype)
                return out.at[jnp.minimum(dst, num_rows)].max(
                    messages)[:num_rows]
            out = jnp.zeros((num_rows + 1, messages.shape[-1]),
                            messages.dtype)
            return out.at[jnp.minimum(dst, num_rows)].add(messages)[:num_rows]
    if backend not in AGG_BACKENDS:
        raise ValueError(f"unknown aggregate backend {backend!r}; "
                         f"options: {AGG_BACKENDS}")
    assert (edge_order is not None and local_dst is not None
            and block_tile is not None), (
        "tiled/pallas backends need the prepare_tiled_edges layout")
    if edge_order.shape[-1] == 0 and messages.shape[0] > 0:
        raise ValueError(
            "empty tiled layout: the partition book / sample plan was built "
            "without tiled_layout=True but a tiled backend was requested")
    use_pallas = None if backend == "tiled" else True
    fn = _tiled_aggregate_max if reduce == "max" else _tiled_aggregate
    return fn(
        num_rows, tile_v, block_e, use_pallas, interpret,
        messages, dst, edge_order, local_dst, block_tile,
    )


# ---------------------------------------------------------------------------
# edge_softmax — GAT's attention-weighted aggregate, one edge pass
# ---------------------------------------------------------------------------

# edges per step of the softmax backward's loop: bounds its per-edge
# transients ([chunk, W] f32) whatever the graph's size
SOFTMAX_BWD_CHUNK = 1 << 17


def attention_scores(z: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """Per-head scores a_h . z_h of rows `z` [n, H*dh] for `a` [H, dh]:
    [n, H], in full float32 (the kernel scores its source rows so too)."""
    heads, dh = a.shape
    return jnp.einsum("nhd,hd->nh", z.reshape(-1, heads, dh), a,
                      precision=jax.lax.Precision.HIGHEST)


def _per_lane(t: jnp.ndarray, dh: int) -> jnp.ndarray:
    """[n, H] per-head values repeated over each head's dh lanes."""
    n, heads = t.shape
    return jnp.broadcast_to(t[:, :, None], (n, heads, dh)).reshape(
        n, heads * dh)


def softmax_rescale(part: tuple, m_new: jnp.ndarray) -> tuple:
    """An (m, den, num) partial of an edge softmax shifted to the row max
    `m_new` (at least its own m). Neither max carries a gradient: the
    rescale is exact, and softmax does not depend on the shift."""
    m, den, num = part
    m_new = jax.lax.stop_gradient(m_new)
    scale = jnp.exp(jax.lax.stop_gradient(m) - m_new)
    dh = num.shape[-1] // m.shape[-1]
    return m_new, den * scale, num * _per_lane(scale, dh)


def softmax_edge(score: jnp.ndarray, z: jnp.ndarray) -> tuple:
    """The (m, den, num) partial of one edge a row: its score [n, H] and
    source rows z [n, H*dh] (GAT's self edge). Its weight is
    exp(score - score) = 1, which still carries the score's gradient."""
    m = jax.lax.stop_gradient(score)
    w = jnp.exp(score - m)
    return m, w, z * _per_lane(w, z.shape[-1] // score.shape[-1])


def softmax_merge(a: tuple, b: tuple) -> tuple:
    """Merge two (m, den, num) partials of one edge softmax over disjoint
    edge sets (a self edge enters as `softmax_edge`)."""
    m = jnp.maximum(jax.lax.stop_gradient(a[0]), jax.lax.stop_gradient(b[0]))
    _, d1, n1 = softmax_rescale(a, m)
    _, d2, n2 = softmax_rescale(b, m)
    return m, d1 + d2, n1 + n2


def _softmax_scatter(z, a_src, s_dst, src, dst, mask, num_rows):
    """The scatter oracle: a segment-max, then two segment-sums."""
    heads, dh = a_src.shape
    dstc = jnp.minimum(dst, num_rows)
    pre = (attention_scores(z, a_src)[src]
           + jnp.pad(s_dst, ((0, 1), (0, 0)))[dstc])
    s = jnp.where(mask[:, None], jax.nn.leaky_relu(pre, 0.2), -jnp.inf)
    m = jnp.full((num_rows + 1, heads), SOFTMAX_FLOOR, s.dtype)
    m = jax.lax.stop_gradient(m.at[dstc].max(s))
    w = jnp.exp(s - m[dstc])
    den = jnp.zeros((num_rows + 1, heads), w.dtype).at[dstc].add(w)
    msg = (w[:, :, None] * z[src].reshape(-1, heads, dh)).reshape(
        -1, heads * dh)
    num = jnp.zeros((num_rows + 1, heads * dh), msg.dtype).at[dstc].add(msg)
    return m[:num_rows], den[:num_rows], num[:num_rows]


def _softmax_tile_f(w: int, dh: int) -> int:
    """A feature tile of whole heads: 128 lanes where they hold whole
    heads, a head where it spans 128-lane multiples, else every lane."""
    if w % DEFAULT_TILE_F == 0 and DEFAULT_TILE_F % dh == 0:
        return DEFAULT_TILE_F
    if dh % DEFAULT_TILE_F == 0:
        return dh
    return w


def _softmax_kernel(num_rows, tile_v, block_e, interpret, z, a_src, s_dst,
                    layout_src, local_dst, block_tile, with_pos=False):
    """(m, den, num) of the kernel's softmax combiner, [num_rows, H] for m
    and den and [num_rows, H*dh] for num; `with_pos` adds num_pos and
    den_pos (the sums over the edges whose score is not negative), as num
    and den."""
    heads, dh = a_src.shape
    w = heads * dh
    rows_padded, _ = tiled_shape(num_rows, tile_v)
    tile_f = _softmax_tile_f(w, dh)
    with jax.named_scope("edge.reorder"):
        # one gather from the rows straight into layout order
        ordered = z[layout_src]
    with jax.named_scope("edge.softmax"):
        per_tile = tile_f // dh
        rows = -(-per_tile // 8) * 8
        # a_rows[ft, h] = a_src of the tile's h-th head, in its lanes
        a_lanes = (jnp.eye(rows, per_tile, dtype=a_src.dtype)[:, :, None]
                   * a_src.reshape(w // tile_f, 1, per_tile, dh))
        a_rows = a_lanes.reshape(w // tile_f, rows, tile_f)
        s_lanes = jnp.pad(_per_lane(s_dst, dh),
                          ((0, rows_padded - num_rows), (0, 0)))
        num, den, m, *pos = _softmax_pallas(
            ordered, local_dst, block_tile, a_rows, s_lanes, rows_padded,
            head_dim=dh, block_e=block_e, tile_v=tile_v, tile_f=tile_f,
            with_pos=with_pos, interpret=interpret or not _on_tpu())
        head = lambda t: t[:num_rows, ::dh]  # noqa: E731
        out = (head(m), head(den), num[:num_rows])
        if with_pos:
            out += (pos[0][:num_rows], head(pos[1]))
        return out


def _score_grad(g_den, g_num, den, num, den_pos, num_pos):
    """The destination scores' gradient [n, H] in closed form from the
    forward's sums: leaky_relu' is 0.2 + 0.8 [score >= 0], so the sum over
    a row's edges of ds = w (g_num . z_src + g_den) leaky_relu' is

      0.2 (g_num . num + g_den den) + 0.8 (g_num . num_pos + g_den den_pos)
    """
    heads = den.shape[1]

    def dot(a, b):
        return (a * b).reshape(a.shape[0], heads, -1).sum(-1)

    return (0.2 * (dot(g_num, num) + g_den * den)
            + 0.8 * (dot(g_num, num_pos) + g_den * den_pos))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _tiled_softmax(num_rows, tile_v, block_e, interpret, symmetric, z, a_src,
                   s_dst, src, dst, mask, layout_src, local_dst, block_tile):
    """The edge softmax through the tiled layout and the kernel's softmax
    combiner. The layout must drop exactly the masked edges. Without a
    gradient the kernel keeps no sums for the backward."""
    del symmetric, src, dst, mask  # they choose and feed the backward
    return _softmax_kernel(num_rows, tile_v, block_e, interpret, z, a_src,
                           s_dst, layout_src, local_dst, block_tile)


def _tiled_softmax_fwd(num_rows, tile_v, block_e, interpret, symmetric, z,
                       a_src, s_dst, src, dst, mask, layout_src, local_dst,
                       block_tile):
    m, den, num, num_pos, den_pos = _softmax_kernel(
        num_rows, tile_v, block_e, interpret, z, a_src, s_dst, layout_src,
        local_dst, block_tile, with_pos=True)
    # node-sized residuals and the block's own index arrays only
    edges = ((layout_src, local_dst, block_tile) if symmetric
             else (src, dst, mask))
    return (m, den, num), (z, a_src, s_dst, m, den, num, num_pos, den_pos,
                           edges)


@jax.named_scope("edge.softmax")
def _tiled_softmax_bwd(num_rows, tile_v, block_e, interpret, symmetric, res,
                       g):
    """The gradients with respect to z, a_src and s_dst. Each edge's
    weight is recomputed from the saved row max (the forward keeps nothing
    per edge), and with ds = w (g_num[dst] . z_src + g_den[dst])
    leaky_relu'(pre) for each edge:

      dz[src] += w g_num[dst] + ds a_src
      da_src  += ds z_src
      ds_dst   = `_score_grad` of the forward's sums (no edge pass)

    The first two take one kernel pass over the forward's layout where the
    edge list is its own transpose (`_softmax_reverse`), else a chunked
    loop of gathers and scatter-adds (`_softmax_chunks`)."""
    z, a_src, s_dst, m, den, num, num_pos, den_pos, edges = res
    _, g_den, g_num = g
    table = jnp.concatenate([g_num, s_dst, m, g_den], axis=1)
    if symmetric:
        get_tracer().add("edge.softmax.reverse_pass", 1)
        dz, da = _softmax_reverse(num_rows, tile_v, block_e, interpret, z,
                                  a_src, table, *edges)
    else:
        dz, da = _softmax_chunks(num_rows, z, a_src, table, *edges)
    dsd = _score_grad(g_den, g_num, den, num, den_pos, num_pos)
    return dz, da, dsd, None, None, None, None, None, None


_tiled_softmax.defvjp(_tiled_softmax_fwd, _tiled_softmax_bwd)


def _softmax_reverse(num_rows, tile_v, block_e, interpret, z, a_src, table,
                     layout_src, local_dst, block_tile):
    """dz and da_src in one pass of `segment_softmax_bwd` over the
    forward's layout. The edge list is its own transpose, so the layout
    edges of row r are the edges out of r: one gather of the neighbours'
    `table` rows [g_num | s_dst | m | g_den] into layout order, and the
    kernel sums w g_num and ds over each row's reverse edges."""
    heads, dh = a_src.shape
    w = heads * dh
    rows_padded, _ = tiled_shape(num_rows, tile_v)
    tile_f = _softmax_tile_f(w, dh)
    per_tile = tile_f // dh
    if tile_f == w:
        # one feature tile: the tail follows g_num in the tile's own block
        tail, tail_block, first = table.shape[1], 0, w
    else:
        # the tail in a lane block of its own after g_num
        assert 3 * heads <= DEFAULT_TILE_F, (
            f"{heads} heads: their 3 score columns overflow a lane block")
        tail, tail_block, first = DEFAULT_TILE_F, w // DEFAULT_TILE_F, 0
        # padded before the gather, behind a barrier: XLA would otherwise
        # pad the gathered copy, and hold both
        table = jax.lax.optimization_barrier(
            jnp.pad(table, ((0, 0), (0, tail - 3 * heads))))
    # select[ft, k*per_tile + h] picks quantity k of tile ft's head h;
    # rows padded to a sublane multiple pick nothing
    k, h = np.divmod(np.arange(3 * per_tile), per_tile)
    cols = first + k * heads + np.arange(w // tile_f)[:, None] * per_tile + h
    cols = np.pad(cols, ((0, 0), (0, -cols.shape[1] % 8)),
                  constant_values=-1)
    select = jnp.asarray(cols[:, :, None] == np.arange(tail), jnp.float32)
    s_src = attention_scores(z, a_src)
    pad = ((0, rows_padded - num_rows), (0, 0))
    with jax.named_scope("edge.reorder"):
        ordered = table[layout_src]
    part, sums = _softmax_bwd_pallas(
        ordered, local_dst, block_tile, select, jnp.pad(z, pad),
        jnp.pad(_per_lane(s_src, dh), pad), rows_padded, head_dim=dh,
        tail_block=tail_block, block_e=block_e, tile_v=tile_v,
        tile_f=tile_f, interpret=interpret or not _on_tpu())
    ds = sums[:num_rows, ::dh]
    dz = part[:num_rows] + _per_lane(ds, dh) * a_src.reshape(1, w)
    da = jnp.einsum("nh,nhd->hd", ds, z.reshape(-1, heads, dh),
                    precision=jax.lax.Precision.HIGHEST)
    return dz, da


def _softmax_chunks(num_rows, z, a_src, table, src, dst, mask):
    """dz and da_src chunk by chunk over the edges, each chunk one gather
    of its source rows [z | s_src], one of its destination rows
    [g_num | s_dst | m | g_den] and a scatter-add into the source rows."""
    heads, dh = a_src.shape
    w_all = heads * dh
    e = src.shape[0]
    chunk = min(SOFTMAX_BWD_CHUNK, max(e, 1))
    pad = -e % chunk
    src_c = jnp.pad(src, (0, pad)).reshape(-1, chunk)
    dst_c = jnp.pad(dst, (0, pad), constant_values=num_rows).reshape(
        -1, chunk)
    mask_c = jnp.pad(mask, (0, pad)).reshape(-1, chunk)
    src_rows = jnp.concatenate([z, attention_scores(z, a_src)], axis=1)
    dst_rows = jnp.pad(table, ((0, 1), (0, 0)))  # a zero pad row

    def body(carry, xs):
        dz, da = carry
        s_i, d_i, k_i = xs
        sr, dr = src_rows[s_i], dst_rows[jnp.minimum(d_i, num_rows)]
        zs = sr[:, :w_all].reshape(-1, heads, dh)
        gn = dr[:, :w_all].reshape(-1, heads, dh)
        sd, md, gd = (dr[:, w_all + i * heads:w_all + (i + 1) * heads]
                      for i in range(3))
        pre = sr[:, w_all:] + sd
        w = jnp.exp(jnp.where(k_i[:, None],
                              jax.nn.leaky_relu(pre, 0.2) - md, -jnp.inf))
        ds = (w * (jnp.einsum("ehd,ehd->eh", gn, zs) + gd)
              * jnp.where(pre >= 0, 1.0, 0.2))
        dzs = w[:, :, None] * gn + ds[:, :, None] * a_src
        dz = dz.at[s_i].add(dzs.reshape(-1, w_all))
        return (dz, da + jnp.einsum("eh,ehd->hd", ds, zs)), None

    (dz, da), _ = jax.lax.scan(
        body, (jnp.zeros_like(z), jnp.zeros_like(a_src)),
        (src_c, dst_c, mask_c))
    return dz, da


def edge_softmax(
    z: jnp.ndarray,        # [n, H*dh] source rows (heads concatenated)
    a_src: jnp.ndarray,    # [H, dh] source attention vectors
    s_dst: jnp.ndarray,    # [num_rows, H] destination scores
    src: jnp.ndarray,      # [E] int32 source row of each edge (< n)
    dst: jnp.ndarray,      # [E] int32 destination row (< num_rows)
    num_rows: int,
    *,
    mask: jnp.ndarray,     # [E] bool; False edges take no part
    edge_order: jnp.ndarray | None = None,  # from prepare_tiled_edges
    local_dst: jnp.ndarray | None = None,
    block_tile: jnp.ndarray | None = None,
    backend: str = "scatter",
    tile_v: int = DEFAULT_TILE_V,
    block_e: int = DEFAULT_BLOCK_E,
    interpret: bool = False,
) -> tuple:
    """GAT's edge softmax over the edges into each row, per head:

      s_e = leaky_relu(a_src . z[src_e] + s_dst[dst_e], 0.2)
      m   = max s_e            den = sum exp(s_e - m)
      num = sum exp(s_e - m) z[src_e]

    Returns (m [num_rows, H], den [num_rows, H], num [num_rows, H*dh]), a
    partial that `softmax_merge` combines with others (a self edge, other
    partitions' edges). Rows no edge reaches give (SOFTMAX_FLOOR, 0, 0).
    `m` carries no gradient (the merge and the quotient num / den do not
    depend on the shift).

    backend: scatter — a segment-max and two segment-sums (the oracle);
    tiled — one pass of the kernel's softmax combiner over the
    `prepare_tiled_edges` layout (built with valid=mask) on a TPU, the
    oracle elsewhere, its source rows gathered straight into layout
    order (`src` composed with `edge_order`); pallas — the kernel
    everywhere (interpreted off a TPU). The kernel path's backward
    recomputes the edge weights from the saved row max and keeps nothing
    per edge between the passes: a chunked loop of scatter-adds here, one
    reverse kernel pass for the lists `edge_softmax_symmetric` takes.
    Scopes: `edge.reorder` (the rows' gathers into layout order) and
    `edge.softmax` (the kernel, its backward, the oracle); counters
    `edge.softmax.calls` (one a traced aggregate) and
    `edge.softmax.reverse_pass` (one a traced backward that takes the
    reverse pass).
    """
    return _edge_softmax(z, a_src, s_dst, src, dst, num_rows, mask,
                         (edge_order, local_dst, block_tile), backend,
                         False, tile_v, block_e, interpret)


def edge_softmax_symmetric(
    z: jnp.ndarray,        # [n, H*dh] the rows (heads concatenated)
    a_src: jnp.ndarray,    # [H, dh] source attention vectors
    s_dst: jnp.ndarray,    # [n, H] destination scores
    esrc: jnp.ndarray,     # [E] int32 one end of each edge (< n)
    edst: jnp.ndarray,     # [E] int32 its other end (< n)
    *,
    mask: jnp.ndarray,     # [E] bool; False edges take no part
    edge_order: jnp.ndarray | None = None,
    local_dst: jnp.ndarray | None = None,
    block_tile: jnp.ndarray | None = None,
    backend: str = "scatter",
    tile_v: int = DEFAULT_TILE_V,
    block_e: int = DEFAULT_BLOCK_E,
    interpret: bool = False,
) -> tuple:
    """`edge_softmax` over both directions of every edge, into the rows of
    z: the list [esrc; edst] -> [edst; esrc] with mask [mask; mask], and
    its layout built so (`prepare_tiled_edges` of [edst; esrc] with that
    valid). The list is its own transpose by construction, so a layout
    edge of row r with neighbour n is also the edge r -> n, and the kernel
    path's backward is one reverse pass over the forward's layout
    (`segment_spmm_softmax_bwd`), with no scatter."""
    return _edge_softmax(
        z, a_src, s_dst, jnp.concatenate([esrc, edst]),
        jnp.concatenate([edst, esrc]), z.shape[0],
        jnp.concatenate([mask, mask]), (edge_order, local_dst, block_tile),
        backend, True, tile_v, block_e, interpret)


def _edge_softmax(z, a_src, s_dst, src, dst, num_rows, mask, layout,
                  backend, symmetric, tile_v, block_e, interpret):
    if backend not in AGG_BACKENDS:
        raise ValueError(f"unknown aggregate backend {backend!r}; "
                         f"options: {AGG_BACKENDS}")
    # one per traced aggregate: counted when jax traces, not per step
    get_tracer().add("edge.softmax.calls", 1)
    if backend == "scatter" or (backend == "tiled" and not _on_tpu()):
        with jax.named_scope("edge.softmax"):
            return _softmax_scatter(z, a_src, s_dst, src, dst, mask,
                                    num_rows)
    edge_order, local_dst, block_tile = layout
    assert (edge_order is not None and local_dst is not None
            and block_tile is not None), (
        "tiled/pallas backends need the prepare_tiled_edges layout")
    layout_src = jnp.concatenate([src, jnp.zeros((1,), src.dtype)])[
        edge_order]
    m, den, num = _tiled_softmax(num_rows, tile_v, block_e, interpret,
                                 symmetric, z, a_src, s_dst, src, dst, mask,
                                 layout_src, local_dst, block_tile)
    return jax.lax.stop_gradient(m), den, num


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("causal", "use_pallas", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    use = _on_tpu() if use_pallas is None else use_pallas
    b, h, sq, d = q.shape
    if use or interpret:
        fold = lambda x: x.reshape(b * h, x.shape[2], d)
        out = _flash_pallas(
            fold(q), fold(k), fold(v), causal=causal,
            interpret=interpret or not _on_tpu(),
        )
        return out.reshape(b, h, sq, d)
    return ref.flash_attention_ref(q, k, v, causal=causal)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def decode_attention(
    q: jnp.ndarray,  # [B, H, D]
    k: jnp.ndarray,  # [B, H, S, D]
    v: jnp.ndarray,
    valid_len: jnp.ndarray,
    *,
    use_pallas: bool | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    use = _on_tpu() if use_pallas is None else use_pallas
    b, h, s, d = k.shape
    if use or interpret:
        out = _decode_pallas(
            q.reshape(b * h, d), k.reshape(b * h, s, d), v.reshape(b * h, s, d),
            valid_len, interpret=interpret or not _on_tpu(),
        )
        return out.reshape(b, h, d)
    return ref.decode_attention_ref(q, k, v, valid_len)
