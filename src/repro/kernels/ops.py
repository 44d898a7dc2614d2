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

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
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
    the surviving max). The GAT hot path wraps this in lax.stop_gradient
    and never runs the backward — the vjp exists for standalone use of
    aggregate(reduce="max").
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
            even on ties). GNN softmax stabilisation — the one max
            on the GAT hot path — does NOT need it: softmax is
            shift-invariant, so the stabilisation max is wrapped in
            lax.stop_gradient at the call sites (gnn/models.py,
            gnn/minibatch.py), which is exact and keeps the backward free of
            any scatter/argmax transpose.

    The tiled layout may drop `valid`-masked edges — forward values still
    match the scatter oracle as long as dropped messages carry the reduce
    identity's certainty: identically zero for sum, at or below every
    surviving score for max (the GAT layers mask scores to -1e30 > -inf,
    and clamp the aggregate before use, so both backends agree after
    clamping). Gradients match too, except that a dropped edge exactly
    TYING the surviving row max gets zero cotangent here (it is not part of
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
