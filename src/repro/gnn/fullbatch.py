"""Distributed full-batch GNN training (vertex-cut halo/dense + 1.5D ring).

The per-device program (models.py + sync.py) is identical across three
execution modes:

  mode="sim"       jax.vmap(axis_name=AXIS) over the stacked [k, ...] blocks
                   — exact SPMD semantics on a single host device. This is
                   how the paper's 4..32-machine experiments run inside this
                   CPU container: the collectives are real (vmap implements
                   them), only the transport is local.
  mode="shard_map" jax.shard_map over a real mesh axis — the production
                   path; also what the multi-pod dry-run lowers.
  k == 1           the single-machine oracle (LocalSync), used as the
                   correctness reference: distributed == single, allclose.

The step is composed from four orthogonal STAGE functions, so partition
layout (EdgePartitionBook vs BlockRowBook), sync strategy (halo / dense /
ring), and execution mode (sim / shard_map) are pluggable axes:

  build_book          partition layout     (edge book | 1.5D block rows)
  build_device_blocks static device state  (Block     | RingBlock)
  make_step_fns       per-device loss/forward closed over the SyncStrategy
  wrap_spmd           SPMD dispatch        (bare | vmap sim | shard_map)

`FullBatchTrainer` is the thin composition of the four; every combination
runs through the same trainer, with the k=1 LocalSync oracle pinning
correctness for all of them (tests/test_gnn_distributed.py, test_ring.py).

The trainer measures, per step: loss, collective bytes (analytic, verified
against dry-run HLO), and per-partition compute cost proxies — feeding the
paper's speedup/memory analysis (core/cost_model.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph
from repro.core.partition_book import (
    BlockRowBook,
    build_blockrow_book,
    build_edge_book,
)
from repro.core.wire import as_codec, codec_grad_reduce
from repro.gnn import models
from repro.gnn.models import GNNSpec
from repro.gnn.sync import (
    build_blocks,
    build_ring_blocks,
    make_sync,
    sync_bytes_per_round,
    sync_wire_bytes_per_round,
)
from repro.obs.trace import get_tracer
from repro.optim import adam_init, adam_update

AXIS = "parts"


def step_donate_argnums(lossless: bool) -> tuple:
    """Donated argnums the jitted full-batch train step declares.

    The lossy (error-feedback) step donates opt_state and the EF carry —
    args 1 and 3 of `step(params, opt_state, blocks, ef)` — so the update
    happens in place; the lossless step keeps the historical undonated
    graph. XLA:CPU cannot alias donated buffers (it warns per compile), so
    donation only engages off-CPU — the documented whitelist in the
    analysis donation rule, which otherwise requires every declared donated
    arg to appear in the executable's `input_output_alias` table.
    """
    if lossless or jax.default_backend() == "cpu":
        return ()
    return (1, 3)


# ---------------------------------------------------------------------------
# Stage 1: partition layout
# ---------------------------------------------------------------------------


def build_book(
    graph: Graph,
    edge_assignment: Optional[np.ndarray],
    k: int,
    *,
    sync_mode: str = "halo",
    tiled_layout: bool = False,
):
    """Choose the static layout for a sync strategy.

    halo/dense/local run on an `EdgePartitionBook` (any edge partitioner);
    ring runs on a `BlockRowBook` (1.5D contiguous blocks — needs no
    partitioning heuristic, so `edge_assignment` is ignored / may be None).
    """
    if sync_mode == "ring":
        return build_blockrow_book(graph, k, tiled_layout=tiled_layout)
    if edge_assignment is None:
        raise ValueError(f"sync mode {sync_mode!r} needs an edge assignment")
    return build_edge_book(graph, edge_assignment, k,
                           tiled_layout=tiled_layout)


# ---------------------------------------------------------------------------
# Stage 2: static device state
# ---------------------------------------------------------------------------


def build_device_blocks(book, features, labels, train_mask):
    """Stacked [k, ...] device blocks matching the book's layout."""
    if isinstance(book, BlockRowBook):
        return build_ring_blocks(book, features, labels, train_mask)
    return build_blocks(book, features, labels, train_mask)


# ---------------------------------------------------------------------------
# Stage 3: per-device programs
# ---------------------------------------------------------------------------


def resolve_sync_mode(sync_mode: str, k: int) -> str:
    """k=1 collapses the partial-aggregate strategies to the LocalSync
    oracle. Ring stays ring: its blocks carry chunk tables, not halo
    tables, and its k=1 loop is already collective-free."""
    if k == 1 and sync_mode != "ring":
        return "local"
    return sync_mode


def make_step_fns(spec: GNNSpec, sync_mode: str, num_vertices: int, k: int,
                  codec=None):
    """(loss_fn, forward_fn), each `(params, blk) -> ...` on ONE device."""
    mode = resolve_sync_mode(sync_mode, k)

    def loss(params, blk):
        sync = make_sync(mode, blk, num_vertices, AXIS, codec=codec)
        return models.loss_fn(spec, params, blk.x, blk, sync)

    def forward(params, blk):
        sync = make_sync(mode, blk, num_vertices, AXIS, codec=codec)
        return models.forward(spec, params, blk.x, blk, sync)

    return loss, forward


# ---------------------------------------------------------------------------
# Stage 4: SPMD dispatch
# ---------------------------------------------------------------------------


def wrap_spmd(fn, k: int, mode: str,
              mesh: Optional[jax.sharding.Mesh] = None, n_mapped: int = 1):
    """Run a (params, *mapped) function in the chosen mode.

    The first argument is replicated (params); the next `n_mapped` arguments
    are stacked [k, ...] per-device trees (blocks, and for the lossy-codec
    train step the per-device error-feedback state as a second carry)."""
    if k == 1:
        return lambda params, *mapped: fn(
            params, *(jax.tree.map(lambda a: a[0], m) for m in mapped)
        )
    if mode == "sim":
        return jax.vmap(fn, in_axes=(None,) + (0,) * n_mapped,
                        axis_name=AXIS)
    assert mesh is not None, "shard_map mode needs a mesh"
    P = jax.sharding.PartitionSpec

    def per_device(params, *mapped_local):
        # shard_map keeps the sharded leading dim as size 1 (vmap strips
        # it) — squeeze in, unsqueeze out
        args = (jax.tree.map(lambda a: a[0], m) for m in mapped_local)
        out = fn(params, *args)
        return jax.tree.map(lambda a: a[None], out)

    return jax.shard_map(per_device, mesh=mesh, check_vma=False,
                         in_specs=(P(),) + (P(AXIS),) * n_mapped,
                         out_specs=P(AXIS))


# ---------------------------------------------------------------------------
# The trainer: composition of the four stages
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FullBatchTrainer:
    spec: GNNSpec
    book: Any                          # EdgePartitionBook | BlockRowBook
    blocks: Any                        # Block | RingBlock, stacked [k, ...]
    sync_mode: str = "halo"            # halo | dense | ring
    mode: str = "sim"                  # sim | shard_map
    mesh: Optional[jax.sharding.Mesh] = None
    params: Any = None
    opt_state: Any = None
    lr: float = 1e-2
    codec: Any = None                  # wire codec name/instance (None=fp32)
    ef_state: Any = None               # error-feedback carry (lossy codecs)

    # ---------------------------------------------------------------- setup
    @classmethod
    def build(
        cls,
        graph: Graph,
        edge_assignment: Optional[np.ndarray],
        k: int,
        spec: GNNSpec,
        features: np.ndarray,
        labels: np.ndarray,
        train_mask: np.ndarray,
        *,
        sync_mode: str = "halo",
        mode: str = "sim",
        mesh: Optional[jax.sharding.Mesh] = None,
        seed: int = 0,
        lr: float = 1e-2,
        codec=None,
    ) -> "FullBatchTrainer":
        book = build_book(
            graph, edge_assignment, k, sync_mode=sync_mode,
            tiled_layout=(spec.agg_backend != "scatter"),
        )
        blocks = build_device_blocks(book, features, labels, train_mask)
        params = models.init_params(spec, seed=seed)
        opt_state = adam_init(params)
        if mode == "shard_map" and k > 1:
            # each device holds its own partition of the stacked blocks and
            # a replica of the model, as the step's outputs will
            P = jax.sharding.PartitionSpec
            shard = functools.partial(jax.sharding.NamedSharding, mesh)
            blocks = jax.device_put(blocks, shard(P(AXIS)))
            params, opt_state = jax.device_put((params, opt_state), shard(P()))
        return cls(
            spec=spec, book=book, blocks=blocks, sync_mode=sync_mode,
            mode=mode, mesh=mesh, params=params, opt_state=opt_state,
            lr=lr, codec=codec,
        )

    # ------------------------------------------------------------- plumbing
    @functools.cached_property
    def _step_fns(self):
        return make_step_fns(self.spec, self.sync_mode,
                             self.book.num_vertices, self.book.k,
                             codec=self.codec)

    def _wrap(self, fn, n_mapped: int = 1):
        return wrap_spmd(fn, self.book.k, self.mode, self.mesh,
                         n_mapped=n_mapped)

    def _init_ef(self):
        """Per-device zero EF residuals, stacked [k, ...] like the blocks."""
        base = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), self.params)
        if self.book.k > 1:
            base = jax.tree.map(
                lambda z: jnp.zeros((self.book.k,) + z.shape, z.dtype), base)
        return base

    # ----------------------------------------------------------------- api
    @functools.cached_property
    def _train_step(self):
        per_device_loss, _ = self._step_fns
        codec = as_codec(self.codec)

        if codec.lossless:
            # historical step graph, untouched: grads via the implicit vmap/
            # shard_map backward of the mean loss (bitwise-identical default)
            def loss_of(params, blocks):
                losses = self._wrap(per_device_loss)(params, blocks)
                return jnp.mean(losses)

            def step(params, opt_state, blocks):
                loss, grads = jax.value_and_grad(loss_of)(params, blocks)
                new_params, new_state = adam_update(
                    grads, opt_state, params, lr=self.lr
                )
                return loss, new_params, new_state

            return jax.jit(step)

        # lossy codec: per-device grads completed by the error-feedback
        # compressed pmean (== the implicit backward's gradient for fp32;
        # verified against it in tests/test_wire.py)
        k = self.book.k
        axis = AXIS if k > 1 else None

        def per_device(params, blk, ef):
            loss, grads = jax.value_and_grad(per_device_loss)(params, blk)
            mean_grads, new_ef = codec_grad_reduce(codec, grads, ef, axis)
            return loss, mean_grads, new_ef

        wrapped = self._wrap(per_device, n_mapped=2)

        def step(params, opt_state, blocks, ef):
            losses, grads, new_ef = wrapped(params, blocks, ef)
            if k > 1:
                # pmean made the grads replica-consistent; lane 0 is the mean
                losses = jnp.mean(losses)
                grads = jax.tree.map(lambda g: g[0], grads)
            new_params, new_state = adam_update(
                grads, opt_state, params, lr=self.lr
            )
            return losses, new_params, new_state, new_ef

        return jax.jit(step, donate_argnums=step_donate_argnums(False))

    @functools.cached_property
    def _forward(self):
        _, per_device_fwd = self._step_fns
        return jax.jit(
            lambda params, blocks: self._wrap(per_device_fwd)(params, blocks)
        )

    def train_step(self) -> float:
        """One epoch. Inside the `fullbatch.step` span, two contiguous
        phases: `fullbatch.step.dispatch` (the call into the jitted step,
        until it returns) and `fullbatch.step.wait` (reading the loss back,
        which waits for the device)."""
        tracer = get_tracer()
        with tracer.span("fullbatch.step", cat="step",
                         args={"sync": self.sync_mode}):
            lossless = as_codec(self.codec).lossless
            if not lossless and self.ef_state is None:
                self.ef_state = self._init_ef()
            clock = tracer.phase_clock(cat="step",
                                       first="fullbatch.step.dispatch")
            if lossless:
                loss, self.params, self.opt_state = self._train_step(
                    self.params, self.opt_state, self.blocks
                )
            else:
                loss, self.params, self.opt_state, self.ef_state = \
                    self._train_step(
                        self.params, self.opt_state, self.blocks,
                        self.ef_state
                    )
            clock.split("fullbatch.step.dispatch", then="fullbatch.step.wait")
            loss = float(loss)
            clock.split("fullbatch.step.wait")
            return loss

    def set_epoch(self, epoch: int) -> None:
        """Advance epoch-scheduled codecs (VariableRatioCodec). Re-jits the
        step only when the schedule actually changes tier."""
        codec = as_codec(self.codec)
        advance = getattr(codec, "at_epoch", None)
        if advance is None:
            return
        new = advance(epoch)
        # a tier change shows up in the per-layer ratios; same ratios mean
        # the same trace, so keep the compiled step
        if (new.ratio(0), new.ratio(1)) != (codec.ratio(0), codec.ratio(1)):
            self.codec = new
            for cached in ("_step_fns", "_train_step", "_forward"):
                self.__dict__.pop(cached, None)
        else:
            self.codec = new

    def forward_logits_global(self) -> np.ndarray:
        """Master-row logits gathered to a global [V, C] array (testing)."""
        out = self._forward(self.params, self.blocks)
        if self.book.k == 1:
            out = out[None]
        return self.book.scatter_to_global(np.asarray(out))

    # ------------------------------------------------------------- accounting
    def comm_bytes_per_epoch(self) -> int:
        """Analytic collective traffic of one full-batch epoch (fwd+bwd).

        Backward of a reduce+broadcast pair is another broadcast+reduce pair;
        backward of a ppermute ring is the reverse ring — either way 2x the
        forward volume. GAT syncs 3 aggregates/layer, SAGE/GCN 1; each
        aggregate is priced at its true payload width
        (`GNNSpec.aggregate_dims`), so the total matches the collectives a
        traced step actually records.
        """
        total = 0
        for layer_dims in self.spec.aggregate_dims(self.sync_mode):
            for d in layer_dims:
                per = sync_bytes_per_round(self.book, d, self.sync_mode)
                total += per * 2  # fwd + bwd
        # gradient all-reduce of the (replicated) model parameters
        n_params = sum(
            int(np.prod(p.shape)) for p in jax.tree.leaves(self.params)
        )
        total += 2 * self.book.k * n_params * 4
        return total

    def wire_bytes_per_epoch(self) -> int:
        """Codec-aware twin of `comm_bytes_per_epoch`: bytes that actually
        cross the network once payloads are encoded (== the logical number
        under the default fp32 codec)."""
        codec = as_codec(self.codec)
        total = 0
        ordinal = 0
        for layer_dims in self.spec.aggregate_dims(self.sync_mode):
            for d in layer_dims:
                per = sync_wire_bytes_per_round(
                    self.book, d, self.sync_mode, codec, layer=ordinal)
                total += per * 2  # fwd + bwd
                ordinal += 1
        # gradient all-reduce, priced per leaf (per-tensor codec meta)
        leaf_bytes = sum(
            codec.wire_bytes(p.shape) for p in jax.tree.leaves(self.params)
        )
        total += 2 * self.book.k * leaf_bytes
        return total

    def memory_bytes_per_partition(self) -> np.ndarray:
        """Analytic per-partition training memory (features + activations +
        graph structure), the quantity behind the paper's Fig. 10/11."""
        k = self.book.k
        f = self.spec.feature_dim
        h = self.spec.hidden_dim
        L = self.spec.num_layers
        verts = self.book.vmask.sum(axis=1)  # true local vertices
        if isinstance(self.book, BlockRowBook):
            edges = self.book.chunk_emask.sum(axis=(1, 2))
            # double-buffered rotation payload instead of halo buckets
            comm_buf = 2 * (self.book.v_block + 1) * max(f, h) * 4
        else:
            edges = self.book.emask.sum(axis=1)
            comm_buf = 2 * k * self.book.bucket * max(f, h) * 4
        feat = verts * f * 4
        # stored activations: one [Vloc, hidden] per layer (backward needs them)
        acts = verts * h * 4 * L
        structure = edges * 2 * 4
        return (feat + acts + structure + comm_buf).astype(np.int64)
