"""Synchronisation strategies for distributed full-batch GNNs.

The `SyncStrategy` protocol is ONE method every model layer builds on:

    edge_aggregate(blk, payload, msg_fn, *, reduce, backend) -> [Vloc+1, d]

Give it per-vertex payload rows and a message function
`msg_fn(src_rows, dst_idx, edge_mask) -> [E, d]`; it returns the COMPLETE
(globally consistent) per-destination aggregate over the symmetrised
adjacency. `reduce="softmax"` is GAT's edge softmax (`ops.edge_softmax`):
the payload is z, `msg_fn` is the pair (a_src, s_dst) of source attention
vectors and local destination scores, and the result is the complete
(max, denominator, numerator) triple, the partials merged by
`ops.softmax_merge` or rescaled to the completed max before their sums.
`psum(v)` completes scalars (the loss). How completion happens — and what
it costs — is the strategy:

  LocalSync  — k=1 oracle: one local `ops.aggregate` pass, nothing moves.
  DenseSync  — aggregate locally, scatter into a global [V+1, d] buffer and
               `psum` it. Volume O(V·d) per sync, *independent of
               partitioning quality* — the naive baseline.
  HaloSync   — aggregate locally, then complete replicas via static-routed
               all_to_all from the partition book's replica lists. One
               reduce+broadcast pair moves 2·k·B·d elements per device
               (B = max pair bucket) = 2·k²·B·d·4 bytes cluster-wide
               (`sync_bytes_per_round`, pinned against compiled HLO in
               tests/test_dist_lowering.py). Volume tracks the replication
               factor — the paper's key mechanism (README architecture map).
  RingSync   — 1.5D block rotation (CAGNET regime, `BlockRowBook`): no
               replicas exist, so nothing is "completed" — instead the
               payload blocks rotate around a `lax.ppermute` ring. Stage s
               aggregates the pre-rotated edge chunk (dst local, src in the
               currently-held block) while the next block is in flight:
               k−1 `ppermute` stages of (V/k + 1)·d elements each per
               device, i.e. k·(k−1)·(V/k + 1)·d·4 bytes cluster-wide per
               aggregate — compare halo's 2·k²·B·d·4 (replication-
               dependent) and dense's 2·k·(V+1)·d·4 (always worst-case).
               Per round, ring < dense for every k ≥ 2 since
               (k−1)/k · V < 2·V; no second broadcast pass is needed
               because block rows are owned exactly once.

Per-aggregate collective volume (cluster-wide; wire bytes are the same
formulas with the f32 element replaced by `codec.wire_bytes`, see
`sync_wire_bytes_per_round`):

    strategy   logical bytes (fp32)          wire bytes (codec c)
    dense      2·k·(V+1)·d·4                 2·k·c.wire_bytes((V+1, d))
    halo       2·k²·B·d·4                    2·k·c.wire_bytes((k, B, d))
    ring       k·(k−1)·(Vb+1)·d·4            k·(k−1)·c.wire_bytes((Vb+1, d))

Every strategy carries a `codec` (repro/core/wire.py, default fp32 ==
today's bytes): payloads encode BEFORE the collective and decode after, so
the compiled HLO moves the compressed dtype — `all_to_all`/`ppermute` of
int8 is ¼ the bytes, pinned in tests/test_dist_lowering.py. The fp32 codec
is the identity, keeping the default trace bitwise-identical to the
pre-codec code. Lossy caveats: DenseSync's `reduce_max` and the -1e30 mask
fills stay f32 (an extreme fill through a per-tensor scale would erase the
signal; the receiver re-masks, so fills never influence results anyway).

Local/Dense/Halo additionally keep their historical low-level surface
(`reduce_sum` / `reduce_max` / `broadcast`) — partial-aggregate completion —
which `edge_aggregate` composes; RingSync has no such decomposition (the
communication IS the aggregation loop).

All strategies work identically under `jax.vmap(axis_name=...)` (CPU
simulation of k workers) and `jax.shard_map` (real meshes / the multi-pod
dry-run), because they only use axis-name collectives.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition_book import BlockRowBook, EdgePartitionBook
from repro.core.wire import Codec, as_codec
from repro.kernels import ops
from repro.obs.trace import get_tracer


def _nbytes(x) -> int:
    """Static byte size of an array/tracer from its aval (shape/dtype are
    concrete even under vmap/jit tracing; scalars count their itemsize)."""
    if x is None:
        return 0
    return int(np.prod(x.shape)) * int(np.dtype(x.dtype).itemsize)


def _record_collective(kind: str, cluster_bytes: int,
                       wire_bytes: Optional[int] = None, *,
                       layer: int = 0) -> None:
    """Report one collective to the installed tracer at jax TRACE time.

    Fires once per compilation (not per executed step) — the runtime
    reconciliation gate compares these single-trace totals against
    `collective_budget`/`sync_wire_bytes_per_round` for one forward pass.
    Loss-scalar psums are deliberately not recorded: the budget's scope is
    "per complete aggregate", matching the static gate.
    """
    tr = get_tracer()
    if tr.enabled:
        tr.collective(kind, cluster_bytes, wire_bytes=wire_bytes,
                      layer=layer)


class Block(NamedTuple):
    """One partition's static device block (all jnp arrays, pytree-able).

    Leading [k, ...] when stacked for vmap/shard_map; per-device inside.
    """

    x: jnp.ndarray           # [Vloc+1, F] features
    labels: jnp.ndarray      # [Vloc+1] int32 (-1 pad)
    train_mask: jnp.ndarray  # [Vloc+1] bool
    esrc: jnp.ndarray        # [Eloc] int32
    edst: jnp.ndarray        # [Eloc] int32
    emask: jnp.ndarray       # [Eloc] bool
    degree: jnp.ndarray      # [Vloc+1] float32 (global symmetric degree)
    master: jnp.ndarray      # [Vloc+1] bool
    vmask: jnp.ndarray       # [Vloc+1] bool
    send_idx: jnp.ndarray    # [k, B] int32
    send_mask: jnp.ndarray   # [k, B] bool
    recv_idx: jnp.ndarray    # [k, B] int32
    recv_mask: jnp.ndarray   # [k, B] bool
    vglobal: jnp.ndarray     # [Vloc+1] int32 (pad -> V, the global dummy row)
    # tiled aggregation layout over the symmetrised edge list [edst | esrc]
    # (kernels.ops.prepare_tiled_edges; used by the tiled/pallas backends)
    agg_order: jnp.ndarray   # [E_tiled] int32 (pad -> 2*Eloc)
    agg_ldst: jnp.ndarray    # [E_tiled] int32 (pad -> tile_v)
    agg_btile: jnp.ndarray   # [E_tiled / block_e] int32 row tile per block


def build_blocks(
    book: EdgePartitionBook,
    features: np.ndarray,
    labels: np.ndarray,
    train_mask: np.ndarray,
) -> Block:
    """Stacked [k, ...] Block from a partition book + global node data."""
    x = book.local_features(features.astype(np.float32))
    # one dummy row is already included (index v_max)
    lab = book.local_labels(labels.astype(np.int32))
    tm = np.zeros((book.k, book.v_max + 1), dtype=bool)
    safe = np.where(book.vglobal >= 0, book.vglobal, 0)
    tm[:] = train_mask[safe]
    tm &= book.vmask
    vg = np.where(book.vglobal >= 0, book.vglobal, book.num_vertices)
    return Block(
        x=jnp.asarray(x),
        labels=jnp.asarray(lab),
        train_mask=jnp.asarray(tm),
        esrc=jnp.asarray(book.esrc),
        edst=jnp.asarray(book.edst),
        emask=jnp.asarray(book.emask),
        degree=jnp.asarray(book.degree),
        master=jnp.asarray(book.master),
        vmask=jnp.asarray(book.vmask),
        send_idx=jnp.asarray(book.send_idx),
        send_mask=jnp.asarray(book.send_mask),
        recv_idx=jnp.asarray(book.recv_idx),
        recv_mask=jnp.asarray(book.recv_mask),
        vglobal=jnp.asarray(vg.astype(np.int32)),
        agg_order=jnp.asarray(book.agg_order),
        agg_ldst=jnp.asarray(book.agg_ldst),
        agg_btile=jnp.asarray(book.agg_btile),
    )


# ---------------------------------------------------------------------------
# SyncStrategy protocol
# ---------------------------------------------------------------------------


class _CodecSync:
    """Wire-codec plumbing shared by every strategy.

    Strategies are frozen dataclasses, so the trace-time aggregate counter
    lives behind `object.__setattr__`. `models.forward` resets it at the
    top of each forward pass, making the ordinal "which aggregate of this
    forward is encoding" — the depth signal `VariableRatioCodec` ramps on.
    Python-level state only: it is fixed at trace time, never a tracer.
    """

    def _codec(self) -> Codec:
        return as_codec(getattr(self, "codec", None))

    def reset_layer_counter(self) -> None:
        object.__setattr__(self, "_agg_layer", 0)

    def _take_layer(self) -> int:
        layer = int(getattr(self, "_agg_layer", 0))
        object.__setattr__(self, "_agg_layer", layer + 1)
        return layer


class _PartialAggSync(_CodecSync):
    """Shared `edge_aggregate` for the partial-aggregate family.

    Local/Dense/Halo all follow the same recipe: reduce messages over the
    symmetrised local edge list (both directions of every stored edge, via
    `ops.aggregate` so scatter/tiled/pallas backends all serve), then
    complete the per-partition partials with the strategy's reduce+broadcast
    pair. `msg_fn(src_rows, dst_idx, edge_mask)` sees the payload rows
    gathered at the edge's source and the LOCAL destination index (for
    destination-side tables such as GAT's softmax shift).
    """

    def edge_aggregate(self, blk: "Block", payload, msg_fn, *,
                       reduce: str = "sum", backend: str = "scatter"):
        if reduce == "softmax":
            return self._edge_softmax(blk, payload, msg_fn, backend)
        object.__setattr__(self, "_cur_layer", self._take_layer())
        n = payload.shape[0]
        with jax.named_scope("edge.gather"):
            messages = jnp.concatenate([
                msg_fn(payload[blk.esrc], blk.edst, blk.emask),
                msg_fn(payload[blk.edst], blk.esrc, blk.emask),
            ], axis=0)
            dst = jnp.concatenate([blk.edst, blk.esrc], axis=0)
        agg = ops.aggregate(
            messages, dst, n,
            edge_order=blk.agg_order, local_dst=blk.agg_ldst,
            block_tile=blk.agg_btile,
            backend=backend, reduce=reduce,
        )
        agg = self.reduce_max(agg) if reduce == "max" else self.reduce_sum(agg)
        return self.broadcast(agg)

    def _complete(self, h, reduce_fn):
        object.__setattr__(self, "_cur_layer", self._take_layer())
        return self.broadcast(reduce_fn(h))

    def _edge_softmax(self, blk: "Block", z, scores, backend: str):
        """The local edge softmax partial over the symmetrised edge list,
        completed as three aggregates: the row max, then the denominator
        and the numerator, each rescaled to the completed max first."""
        a_src, s_dst = scores
        m, den, num = ops.edge_softmax_symmetric(
            z, a_src, s_dst, blk.esrc, blk.edst, mask=blk.emask,
            edge_order=blk.agg_order, local_dst=blk.agg_ldst,
            block_tile=blk.agg_btile, backend=backend)
        m_all = self._complete(m, self.reduce_max)
        m_all, den, num = ops.softmax_rescale((m, den, num), m_all)
        return (m_all, self._complete(den, self.reduce_sum),
                self._complete(num, self.reduce_sum))


@dataclasses.dataclass(frozen=True)
class LocalSync(_PartialAggSync):
    """k=1: partial aggregates are already complete (codec: nothing moves)."""

    codec: Optional[Union[str, Codec]] = None

    def reduce_sum(self, h):
        return h

    def reduce_max(self, h):
        return h

    def broadcast(self, h):
        return h

    def psum(self, v):
        return v


@dataclasses.dataclass(frozen=True)
class DenseSync(_PartialAggSync):
    """Naive baseline: materialise the global vertex state and psum it."""

    blk: Block
    num_vertices: int
    axis: str
    codec: Optional[Union[str, Codec]] = None

    def _to_global(self, h):
        g = jnp.zeros((self.num_vertices + 1, h.shape[-1]), h.dtype)
        g = g.at[self.blk.vglobal].add(h * self.blk.vmask[:, None])
        return g

    def reduce_sum(self, h):
        codec = self._codec()
        g = self._to_global(h)
        if not codec.lossless:
            # quantise the per-device partial BEFORE the psum (the reduce
            # sums dequantised views — same semantics as compressed_psum)
            payload, meta = codec.encode(
                g, layer=getattr(self, "_cur_layer", 0))
            g = codec.decode(payload, meta)
        # wire_bytes=None: the reduce moves the DEQUANTISED f32 view, so
        # the transport-model formula (2x encoded) intentionally diverges
        _record_collective("all-reduce",
                           self.blk.send_idx.shape[0] * _nbytes(g),
                           layer=getattr(self, "_cur_layer", 0))
        g = jax.lax.psum(g, self.axis)
        return g[self.blk.vglobal] * self.blk.vmask[:, None]

    def reduce_max(self, h):
        g = jnp.full((self.num_vertices + 1, h.shape[-1]), -1e30, h.dtype)
        g = g.at[self.blk.vglobal].max(jnp.where(self.blk.vmask[:, None], h, -1e30))
        _record_collective("all-reduce",
                           self.blk.send_idx.shape[0] * _nbytes(g),
                           layer=getattr(self, "_cur_layer", 0))
        g = jax.lax.pmax(g, self.axis)
        return jnp.where(self.blk.vmask[:, None], g[self.blk.vglobal], h)

    def broadcast(self, h):
        # reduce already produced globally-complete values on every replica
        return h

    def psum(self, v):
        return jax.lax.psum(v, self.axis)


@dataclasses.dataclass(frozen=True)
class HaloSync(_PartialAggSync):
    """Static-routed replica synchronisation (the paper-faithful path).

    reduce_*: every mirror packs its partial rows for each master partition
    into fixed buckets; one all_to_all later, masters scatter-accumulate.
    broadcast: the exact reverse routing pushes completed rows back.

    The codec brackets `_exchange`: the [k, B, d] bucket buffer encodes
    before the all_to_all (the HLO moves the compressed dtype) and decodes
    after. Scale meta is per SENDER, so it travels by `all_gather` — after
    the all_to_all, received bucket j was encoded by device j, i.e. decoded
    with gathered meta[j]. Lossy `reduce_max` sends 0.0 in masked slots
    instead of -1e30 (the receiver re-masks, so the fill is inert either
    way; an extreme fill would destroy a per-tensor int8 scale).
    """

    blk: Block
    axis: str
    codec: Optional[Union[str, Codec]] = None

    def _exchange(self, buf):
        # buf [k, B, d]; result[j] = what device j sent to me
        codec = self._codec()
        lay = getattr(self, "_cur_layer", 0)
        payload, meta = codec.encode(buf, layer=lay)
        k = payload.shape[0]
        pb, mb = _nbytes(payload), _nbytes(meta)
        # cluster bytes follow the HLO output-shape convention (k devices x
        # per-device [k, B, d] payload); wire bytes add the sender meta
        _record_collective("all-to-all", k * pb, k * (pb + mb), layer=lay)
        out = jax.lax.all_to_all(payload, self.axis,
                                 split_axis=0, concat_axis=0)
        if meta is not None:
            # [k] sender scales, ordered by device index == bucket index
            _record_collective("all-gather", k * k * mb, layer=lay)
            meta = jax.lax.all_gather(meta, self.axis).reshape(-1, 1, 1)
        return codec.decode(out, meta)

    def reduce_sum(self, h):
        blk = self.blk
        send = h[blk.send_idx] * blk.send_mask[..., None]
        recv = self._exchange(send)
        # pads point at the dummy row and carry zeros -> harmless adds
        return h.at[blk.recv_idx].add(recv)

    def reduce_max(self, h):
        blk = self.blk
        fill = 0.0 if not self._codec().lossless else -1e30
        send = jnp.where(blk.send_mask[..., None], h[blk.send_idx], fill)
        recv = self._exchange(send)
        return h.at[blk.recv_idx].max(jnp.where(blk.recv_mask[..., None], recv, -1e30))

    def broadcast(self, h):
        blk = self.blk
        send = h[blk.recv_idx] * blk.recv_mask[..., None]
        recv = self._exchange(send)
        current = h[blk.send_idx]
        updated = jnp.where(blk.send_mask[..., None], recv, current)
        return h.at[blk.send_idx].set(updated)

    def psum(self, v):
        return jax.lax.psum(v, self.axis)


# ---------------------------------------------------------------------------
# RingSync (1.5D block rotation over a BlockRowBook)
# ---------------------------------------------------------------------------


class RingBlock(NamedTuple):
    """One block row's static device state (stacked [k, ...] for SPMD).

    Same row layout as `Block` (dummy row at index v_block) so the model
    code is identical; the halo routing tables are replaced by the
    pre-rotated ring chunks.
    """

    x: jnp.ndarray            # [Vb+1, F] features of the OWNED block
    labels: jnp.ndarray       # [Vb+1] int32 (-1 pad)
    train_mask: jnp.ndarray   # [Vb+1] bool
    degree: jnp.ndarray       # [Vb+1] float32 global symmetric degree
    master: jnp.ndarray       # [Vb+1] bool (== vmask: single-owner layout)
    vmask: jnp.ndarray        # [Vb+1] bool
    vglobal: jnp.ndarray      # [Vb+1] int32 (pad -> V)
    # pre-rotated edge chunks: row s = the directed edges whose src lives in
    # the block this device holds at ring stage s (dst indices are local)
    chunk_esrc: jnp.ndarray   # [k, c_max] int32 (pad -> Vb dummy row)
    chunk_edst: jnp.ndarray   # [k, c_max] int32
    chunk_emask: jnp.ndarray  # [k, c_max] bool
    # per-chunk tiled layouts ([k, 0] when built without tiled_layout)
    chunk_agg_order: jnp.ndarray  # [k, E_tiled] int32
    chunk_agg_ldst: jnp.ndarray   # [k, E_tiled] int32
    chunk_agg_btile: jnp.ndarray  # [k, E_tiled / block_e] int32


def build_ring_blocks(
    book: BlockRowBook,
    features: np.ndarray,
    labels: np.ndarray,
    train_mask: np.ndarray,
) -> RingBlock:
    """Stacked [k, ...] RingBlock from a 1.5D book + global node data."""
    x = book.local_features(features.astype(np.float32))
    lab = book.local_labels(labels.astype(np.int32))
    tm = np.zeros((book.k, book.v_block + 1), dtype=bool)
    safe = np.where(book.vglobal >= 0, book.vglobal, 0)
    tm[:] = train_mask[safe]
    tm &= book.vmask
    vg = np.where(book.vglobal >= 0, book.vglobal, book.num_vertices)
    return RingBlock(
        x=jnp.asarray(x),
        labels=jnp.asarray(lab),
        train_mask=jnp.asarray(tm),
        degree=jnp.asarray(book.degree),
        master=jnp.asarray(book.vmask),
        vmask=jnp.asarray(book.vmask),
        vglobal=jnp.asarray(vg.astype(np.int32)),
        chunk_esrc=jnp.asarray(book.chunk_esrc),
        chunk_edst=jnp.asarray(book.chunk_edst),
        chunk_emask=jnp.asarray(book.chunk_emask),
        chunk_agg_order=jnp.asarray(book.chunk_agg_order),
        chunk_agg_ldst=jnp.asarray(book.chunk_agg_ldst),
        chunk_agg_btile=jnp.asarray(book.chunk_agg_btile),
    )


@dataclasses.dataclass(frozen=True)
class RingSync(_CodecSync):
    """1.5D ring-pipelined aggregation (CAGNET-style block rotation).

    At stage s device p holds block (p+s) mod k of the payload; the matching
    pre-rotated chunk (static index s — no dynamic gather of chunk tables)
    is aggregated locally while `lax.ppermute` ships the NEXT block, so the
    transfer overlaps the segment-SpMM. k−1 permutes of [Vb+1, d] per
    aggregate; no reduce/broadcast pair exists because every row is owned
    exactly once.

    The codec encodes the block ONCE before the loop; the encoded
    (payload, meta) pair is what rotates — every hop ships the compressed
    dtype (no re-encode drift: each device decodes the same bits), and the
    stage decodes only the view it aggregates. With fp32 the encode/decode
    are identity and the trace is exactly the historical one.
    """

    axis: str
    k: int
    codec: Optional[Union[str, Codec]] = None

    def _perm(self):
        # device j hands its current block to j-1: after s hops, device p
        # holds block (p+s) mod k — matching chunk (p, s)'s src block
        return [(j, (j - 1) % self.k) for j in range(self.k)]

    def edge_aggregate(self, blk: RingBlock, payload, msg_fn, *,
                       reduce: str = "sum", backend: str = "scatter"):
        codec = self._codec()
        n = payload.shape[0]
        tiled = blk.chunk_agg_order.shape[-1] > 0
        lay = self._take_layer()
        buf, meta = codec.encode(payload, layer=lay)
        acc = None
        for s in range(self.k):
            # issue the transfer BEFORE this stage's compute: XLA schedules
            # the collective-permute-start/done pair around the SpMM
            if s < self.k - 1:
                _record_collective("collective-permute",
                                   self.k * _nbytes(buf),
                                   self.k * _nbytes(buf), layer=lay)
                nxt = jax.lax.ppermute(buf, self.axis, self._perm())
                if meta is not None:
                    _record_collective("collective-permute",
                                       self.k * _nbytes(meta),
                                       self.k * _nbytes(meta), layer=lay)
                nxt_meta = (jax.lax.ppermute(meta, self.axis, self._perm())
                            if meta is not None else None)
            else:
                nxt = nxt_meta = None
            cur = codec.decode(buf, meta)
            layout = dict(
                edge_order=blk.chunk_agg_order[s] if tiled else None,
                local_dst=blk.chunk_agg_ldst[s] if tiled else None,
                block_tile=blk.chunk_agg_btile[s] if tiled else None,
                backend=backend)
            if reduce == "softmax":
                a_src, s_dst = msg_fn
                part = ops.edge_softmax(
                    cur, a_src, s_dst, blk.chunk_esrc[s], blk.chunk_edst[s],
                    n, mask=blk.chunk_emask[s], **layout)
            else:
                messages = msg_fn(cur[blk.chunk_esrc[s]], blk.chunk_edst[s],
                                  blk.chunk_emask[s])
                part = ops.aggregate(messages, blk.chunk_edst[s], n,
                                     reduce=reduce, **layout)
            if acc is None:
                acc = part
            elif reduce == "softmax":
                acc = ops.softmax_merge(acc, part)
            else:
                acc = jnp.maximum(acc, part) if reduce == "max" else acc + part
            if nxt is not None:
                buf, meta = nxt, nxt_meta
        return acc

    def psum(self, v):
        if self.k == 1:
            return v
        return jax.lax.psum(v, self.axis)


SYNC_MODES = ("local", "dense", "halo", "ring")


def make_sync(mode: str, blk, num_vertices: int, axis: str, codec=None):
    """Instantiate a SyncStrategy. `blk` is a `Block` for local/dense/halo
    and a `RingBlock` for ring (1.5D layouts have no halo tables).
    `codec` is a name or `repro.core.wire.Codec` (None -> fp32)."""
    codec = as_codec(codec)
    if mode == "local":
        return LocalSync(codec=codec)
    if mode == "dense":
        return DenseSync(blk=blk, num_vertices=num_vertices, axis=axis,
                         codec=codec)
    if mode == "halo":
        return HaloSync(blk=blk, axis=axis, codec=codec)
    if mode == "ring":
        if not isinstance(blk, RingBlock):
            raise TypeError(
                "sync mode 'ring' needs a RingBlock (build_ring_blocks over "
                f"a BlockRowBook); got {type(blk).__name__}")
        return RingSync(axis=axis, k=int(blk.chunk_esrc.shape[0]),
                        codec=codec)
    raise ValueError(
        f"unknown sync mode {mode!r}: valid strategies are "
        f"{', '.join(SYNC_MODES)}")


def sync_bytes_per_round(book, d: int, mode: str) -> int:
    """Analytic collective volume of ONE complete aggregate, all devices.

    For halo/dense that is a reduce+broadcast pair; for ring it is the k−1
    `ppermute` stages. Used by the study harness and checked against the
    dry-run HLO (tests/test_dist_lowering.py).
    """
    if mode == "halo":
        # each of k devices sends a [k, B, d] f32 buffer per all_to_all and a
        # reduce+broadcast pair is 2 exchanges: 2·k²·B·d·4 bytes cluster-wide
        # (= 2·k·B·d elements per device, as the HaloSync docstring states)
        return 2 * book.k * book.k * book.bucket * d * 4
    if mode == "dense":
        # psum of [V+1, d] on k devices (ring all-reduce ~ 2x payload)
        return 2 * book.k * (book.num_vertices + 1) * d * 4
    if mode == "ring":
        # k-1 ppermute stages, each device shipping its [Vb+1, d] f32 block
        if not isinstance(book, BlockRowBook):
            raise TypeError("ring volume needs a BlockRowBook")
        return book.k * (book.k - 1) * (book.v_block + 1) * d * 4
    return 0


def ring_bytes_per_round(book: BlockRowBook, d: int) -> int:
    """Cluster-wide `ppermute` bytes of one ring aggregate (k·(k−1)·(Vb+1)·d·4)."""
    return sync_bytes_per_round(book, d, "ring")


def collective_budget(book, d: int, mode: str, codec=None,
                      layer: int = 0) -> dict:
    """Predicted compiled-HLO collective budget of ONE complete aggregate.

    The analysis subsystem's collective-budget rule compiles one aggregate
    per (sync_mode, codec) cell under shard_map and holds the HLO to this
    prediction — per collective KIND (the HLO op name), an exact/ranged op
    count and the exact cluster-wide payload bytes under the parser's
    output-shape convention (repro.analysis.hlo):

      halo   2 all_to_alls (reduce+broadcast pair); each op's per-device
             output is the [k, B, d] bucket buffer in the codec's wire
             dtype. Lossy codecs with scale meta gather sender scales
             separately: +2 all-gathers of [k] f32.
      ring   k−1 ppermute stages; payload AND meta rotate via
             collective-permute, so the kind total equals
             `sync_wire_bytes_per_round` exactly. Codecs with meta may
             lower the scale as a separate permute per stage, so the op
             count lands in [k−1, 2(k−1)].
      dense  1 all-reduce of the global [V+1, d] buffer. DenseSync
             quantises then psums the DEQUANTISED view, so the wire stays
             f32 for every codec; the HLO output-shape convention counts
             the reduce once (the analytic formula's ring-allreduce 2x is
             a transport model, not an op count).

    Returns {kind: {"count": (lo, hi), "cluster_bytes": int}}.
    """
    codec = as_codec(codec)
    elem = int(np.dtype(codec.wire_dtype(layer=layer)).itemsize)
    k = book.k

    def wb(shape):
        try:
            return codec.wire_bytes(shape, layer=layer)
        except TypeError:
            return codec.wire_bytes(shape)

    if mode == "halo":
        b = book.bucket
        budget = {"all-to-all": {
            "count": (2, 2),
            "cluster_bytes": 2 * k * k * b * d * elem,
        }}
        meta = wb((k, b, d)) - k * b * d * elem  # per-tensor scale bytes
        if meta > 0:
            # each exchange all_gathers the k sender scales ([k] f32)
            budget["all-gather"] = {"count": (2, 2),
                                    "cluster_bytes": 2 * k * k * meta}
        return budget
    if mode == "ring":
        if not isinstance(book, BlockRowBook):
            raise TypeError("ring budget needs a BlockRowBook")
        has_meta = wb((book.v_block + 1, d)) > (book.v_block + 1) * d * elem
        return {"collective-permute": {
            "count": (k - 1, 2 * (k - 1)) if has_meta else (k - 1, k - 1),
            "cluster_bytes": sync_wire_bytes_per_round(
                book, d, "ring", codec, layer=layer),
        }}
    if mode == "dense":
        return {"all-reduce": {
            "count": (1, 1),
            "cluster_bytes": k * (book.num_vertices + 1) * d * 4,
        }}
    raise ValueError(f"no collective budget for sync mode {mode!r}")


def sync_wire_bytes_per_round(book, d: int, mode: str, codec=None,
                              layer: int = 0) -> int:
    """Codec-aware twin of `sync_bytes_per_round`: bytes that actually cross
    the network for ONE complete aggregate, all devices, after encoding.

    Same collective structure, with each per-device f32 buffer priced by
    `codec.wire_bytes` (payload + meta) instead of nelem·4 — the fp32 codec
    reproduces `sync_bytes_per_round` exactly. `layer` is the aggregate
    ordinal (only `VariableRatioCodec` cares).
    """
    codec = as_codec(codec)

    def wb(shape):
        try:
            return codec.wire_bytes(shape, layer=layer)
        except TypeError:  # fixed-ratio codecs take no layer kwarg
            return codec.wire_bytes(shape)

    if mode == "halo":
        # 2 all_to_alls per round, each device encoding one [k, B, d] buffer
        return 2 * book.k * wb((book.k, book.bucket, d))
    if mode == "dense":
        # psum of the quantised view: ~2x the encoded [V+1, d] buffer per
        # device (ring all-reduce), matching the logical formula's factor
        return 2 * book.k * wb((book.num_vertices + 1, d))
    if mode == "ring":
        if not isinstance(book, BlockRowBook):
            raise TypeError("ring volume needs a BlockRowBook")
        # k-1 ppermute stages per device, each shipping one encoded block
        return book.k * (book.k - 1) * wb((book.v_block + 1, d))
    return 0
