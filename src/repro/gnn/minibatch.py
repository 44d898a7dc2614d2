"""DistDGL-style mini-batch distributed training (vertex partitioning).

Every worker owns one vertex partition (graph + features + its training
vertices). A training step is the paper's five phases (§5.1):

  1. mini-batch sampling   (host, per worker; k-hop fanout sampler)
  2. feature loading       (fetch features of input vertices; *remote*
                            vertices — owned by another worker — cross the
                            network: the paper's key DistDGL metric)
  3. forward pass          (device, data-parallel across workers)
  4. backward pass         (device; gradient all-reduce folded in)
  5. model update          (device)

Phases 1-2 are host work, phases 3-5 one jitted device step — and like
DistDGL's sampler processes they need not run back-to-back: stepping is
delegated to `gnn.pipeline.PipelineEngine` (`overlap`/`prefetch_depth`
knobs on `build`, `--overlap/--prefetch-depth` on launch/gnn_train.py).
Serial mode (`overlap=False`, the default) runs phases 1-2 inline before
every device step — the correctness oracle, with contiguous per-phase
timestamps. Overlap mode prepares batches up to `prefetch_depth` ahead on
a producer thread (per-worker sampling fanned out on a thread pool) while
the device executes the current step; per-worker RNG streams
(`SeedSequence.spawn` per (step, worker)) make both modes produce
bitwise-identical batches from the same seed. `StepMetrics` carries true
wall times for all four host/device phases (sample / fetch / transfer /
compute) plus the step wall and the overlap efficiency (hidden host time
/ total host time), feeding the fig19 phase tables in either mode. The
device step donates params/opt_state buffers (in-place update) on
accelerator backends.

Feature loading (phase 2) is routed through `gnn.feature_store.FeatureStore`:
each worker serves its own shard locally and holds a bounded static cache of
hot remote vertices (``cache_policy`` in {none, random, degree, halo},
``cache_budget`` vertices per worker — see feature_store.py). Per-step
`StepMetrics` therefore splits the paper's `remote_vertices` into
`cache_hits` (served locally from the cache) and `remote_misses` (the only
vertices whose feature bytes cross the network, `miss_bytes`). The cost
model prices the fetch phase from misses; sampling still pays remote
adjacency costs for ALL remote vertices because the cache holds features,
not adjacency.

Aggregation backend (`GNNSpec.agg_backend` in {scatter, tiled, pallas}): the
forward pass aggregates each MFG layer through `kernels.ops.aggregate`
(sums) or `kernels.ops.edge_softmax` (GAT's attention). For the tiled/pallas backends the
host sampler attaches a per-layer tiled edge layout
(`SampledLayer.agg_order`/`agg_ldst`/`agg_btile`, sized by the static pad
plan via
`LayerPad.tiled_plan`) so the device step — compiled once — runs the
pre-sorted segment-reduce instead of a data-dependent scatter; the sum's
backward is a plain gather (custom_vjp in ops.py), so gradients match the
scatter oracle, and so do the edge softmax's (its backward recomputes
the weights from the saved row max).

On this container the k workers are simulated with `jax.vmap(axis_name=...)`
over stacked per-worker batches — identical collective semantics to the
multi-worker `shard_map` deployment. Per-phase times for the paper's cluster
are produced by core/cost_model.py from the *measured* per-worker batch
metrics (input vertices, remote vertices, edges, flops), so the speedup
tables derive from real sampled data, not synthetic assumptions.

Straggler mitigation (beyond-paper, addresses the paper's §5.2(2) imbalance
finding): optional dynamic seed re-balancing shifts seeds from workers whose
sampled computation graphs run persistently large to underloaded ones.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph
from repro.core.partition_book import VertexPartitionBook, build_vertex_book
from repro.core.wire import as_codec, codec_grad_reduce
from repro.gnn.feature_store import FeatureStore
from repro.gnn.pipeline import BatchPreparer, PipelineEngine
from repro.kernels import ops
from repro.obs.trace import get_tracer
from repro.gnn.models import GNNSpec, init_params
from repro.gnn.sampling import PAPER_FANOUTS, SamplePlan

AXIS = "workers"


def step_donate_argnums(lossless: bool) -> tuple:
    """Donated argnums the jitted mini-batch train step declares.

    Lossless: params + opt_state (args 0, 1) — the in-place update the
    module docstring describes. Lossy: opt_state + the EF carry (args 1, 3
    of `step(params, opt_state, stacked, ef)`). XLA:CPU cannot alias
    donated buffers (warns per compile), so donation only engages off-CPU
    — the documented whitelist in the analysis donation rule.
    """
    if jax.default_backend() == "cpu":
        return ()
    return (0, 1) if lossless else (1, 3)


# ---------------------------------------------------------------------------
# Device-side mini-batch model (directed MFG layers + self connection).
# `lay` = dict(esrc, edst, emask, deg, agg_order, agg_ldst, agg_btile); n_dst
# is static
# (from the pad plan). Aggregation targets are sized n_dst+1; index n_dst is
# the padding sink. Every edge aggregation goes through `ops.aggregate`
# (the sums) or `ops.edge_softmax` (GAT's attention, one edge pass), with
# `backend` in {scatter, tiled, pallas}; the tiled layout is per-layer,
# per-batch, shaped by the static pad plan (LayerPad.tiled_plan), so the
# device step still compiles once, and the GAT layer stack runs
# scatter-free under the tiled/pallas backends.
# ---------------------------------------------------------------------------


def _mb_aggregate(messages, lay, n_dst: int, backend: str,
                  reduce: str = "sum"):
    """Reduce per-edge messages into the [n_dst+1, d] destination rows."""
    return ops.aggregate(
        messages, lay["edst"], n_dst + 1,
        edge_order=lay.get("agg_order"), local_dst=lay.get("agg_ldst"),
        block_tile=lay.get("agg_btile"),
        backend=backend, reduce=reduce,
    )


def _mb_sage_layer(p, h_src, lay, n_dst: int, *, final: bool,
                   backend: str = "scatter"):
    msg = h_src[lay["esrc"]] * lay["emask"][:, None]
    agg = _mb_aggregate(msg, lay, n_dst, backend)
    mean = agg[:-1] / jnp.maximum(lay["deg"][:-1], 1.0)[:, None]
    h_self = h_src[:n_dst]
    out = h_self @ p["w_self"] + mean @ p["w_neigh"] + p["b"]
    return out if final else jax.nn.relu(out)


def _mb_gcn_layer(p, h_src, lay, n_dst: int, *, final: bool,
                  backend: str = "scatter"):
    deg_dst = lay["deg"][:-1] + 1.0
    msg = h_src[lay["esrc"]] * lay["emask"][:, None]
    agg = _mb_aggregate(msg, lay, n_dst, backend)
    h = (agg[:-1] + h_src[:n_dst]) / deg_dst[:, None]
    out = h @ p["w"] + p["b"]
    return out if final else jax.nn.relu(out)


def _mb_gat_layer(p, h_src, lay, n_dst: int, *, final: bool,
                  backend: str = "scatter"):
    heads, dh = p["a_src"].shape
    z = h_src @ p["w"]                                 # [n_src, H*dh]
    s_src = ops.attention_scores(z[:n_dst], p["a_src"])
    s_dst = ops.attention_scores(z[:n_dst], p["a_dst"])
    # one edge pass into the n_dst+1 rows (n_dst the padding sink), then
    # the self edge merged in (see models.gat_layer)
    m, den, num = ops.edge_softmax(
        z, p["a_src"], jnp.pad(s_dst, ((0, 1), (0, 0))), lay["esrc"],
        lay["edst"], n_dst + 1, mask=lay["emask"],
        edge_order=lay.get("agg_order"), local_dst=lay.get("agg_ldst"),
        block_tile=lay.get("agg_btile"), backend=backend)
    e_self = jax.nn.leaky_relu(s_src + s_dst, 0.2)
    _, den, num = ops.softmax_merge((m[:-1], den[:-1], num[:-1]),
                                    ops.softmax_edge(e_self, z[:n_dst]))
    out = (num.reshape(n_dst, heads, dh) / den[:, :, None]).reshape(
        n_dst, heads * dh)
    out = (out + p["b"]) @ p["w_out"]
    return out if final else jax.nn.elu(out)


_MB_LAYERS = {"sage": _mb_sage_layer, "gcn": _mb_gcn_layer, "gat": _mb_gat_layer}


def mfg_forward(spec: GNNSpec, layer_params: Sequence, batch,
                layer_sizes: Sequence[int]) -> jnp.ndarray:
    """Forward one padded MFG stack through `layer_params`.

    `layer_params` may be a SUFFIX of the model's layers — the serving
    engine (repro.serve) recomputes only the last `hops` layers on top of
    stored layer-wise embeddings, so `batch["x"]` is then embedding rows,
    not feature rows. The stack always ends at the model's true final layer,
    so the final (no-activation) flag is simply the last entry.
    """
    h = batch["x"]
    layer_fn = _MB_LAYERS[spec.model]
    L = len(layer_params)
    for li, p in enumerate(layer_params):
        h = layer_fn(p, h, batch["layers"][li], layer_sizes[li],
                     final=(li == L - 1), backend=spec.agg_backend)
    return h


def minibatch_loss(spec: GNNSpec, params, batch, layer_sizes: Sequence[int],
                   axis: Optional[str] = AXIS) -> jnp.ndarray:
    """Per-worker loss on one padded MFG stack (psum-averaged over workers)."""
    h = mfg_forward(spec, params["layers"], batch, layer_sizes)
    logits = h[: batch["seed_labels"].shape[0]]
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.maximum(batch["seed_labels"], 0)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    w = (batch["seed_mask"] & (batch["seed_labels"] >= 0)).astype(jnp.float32)
    local = jnp.stack([-(picked * w).sum(), w.sum()])
    tot = jax.lax.psum(local, axis) if axis else local
    return tot[0] / jnp.maximum(tot[1], 1.0)


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepMetrics:
    loss: float
    input_vertices: np.ndarray   # [k]
    remote_vertices: np.ndarray  # [k]
    edges: np.ndarray            # [k]
    sample_time_host: float      # seconds, wall (whole step, all workers)
    compute_time_host: float     # device step (serial: absorbs step overhead
    #                              so the four phases sum to step_wall_host)
    # feature-store phase accounting: remote = cache_hits + remote_misses
    cache_hits: np.ndarray = None      # [k]
    remote_misses: np.ndarray = None   # [k]
    miss_bytes: np.ndarray = None      # [k] logical (f32) miss bytes
    wire_bytes: np.ndarray = None      # [k] codec-encoded miss bytes
    # pipeline phase accounting (gnn/pipeline.py): host wall per phase, the
    # consumer-side step wall, and how much host time the prefetch hid
    fetch_time_host: float = 0.0       # feature gather + stack
    transfer_time_host: float = 0.0    # host -> device
    step_wall_host: float = 0.0        # next_batch + device step, consumer
    queue_wait_host: float = 0.0       # exposed (un-hidden) host time
    overlap: bool = False

    @property
    def host_time(self) -> float:
        """Host prep wall for this batch (sample + fetch + transfer)."""
        return self.sample_time_host + self.fetch_time_host + self.transfer_time_host

    @property
    def overlap_efficiency(self) -> float:
        """Hidden host time / total host time for this step.

        0.0 in serial mode (every host second is exposed before the device
        step); -> 1.0 in overlap steady state when the queue always has a
        batch ready; 1.0 when there was no host work at all."""
        host = self.host_time
        if host <= 0.0:
            return 1.0
        return max(host - self.queue_wait_host, 0.0) / host

    @property
    def hit_rate(self) -> float:
        """Cache hits / remote feature requests, whole step.

        1.0 when the step needed no remote vertices (nothing to miss);
        0.0 when hit accounting is absent (`cache_hits=None`, i.e. no
        feature store was consulted) but remote vertices exist."""
        remote = float(self.remote_vertices.sum())
        if not remote:
            return 1.0
        if self.cache_hits is None:
            return 0.0
        return float(self.cache_hits.sum()) / remote


@dataclasses.dataclass
class MiniBatchTrainer:
    graph: Graph
    book: VertexPartitionBook
    spec: GNNSpec
    features: np.ndarray
    labels: np.ndarray
    train_vertices_per_worker: list
    fanouts: tuple
    plan: SamplePlan
    global_batch: int
    params: Any = None
    opt_state: Any = None
    seed: int = 0
    lr: float = 1e-3
    rebalance: bool = False
    store: Optional[FeatureStore] = None
    overlap: bool = False
    prefetch_depth: int = 2
    codec: Any = None                  # wire codec name/instance (None=fp32)
    ef_state: Any = None               # error-feedback carry (lossy codecs)
    start_step: int = 0                # resume: first global step to draw
    injector: Any = None               # fault.FaultInjector (None = no faults)
    _load_ema: Optional[np.ndarray] = None
    _seed_share: Optional[np.ndarray] = None

    @classmethod
    def build(
        cls,
        graph: Graph,
        vertex_assignment: np.ndarray,
        k: int,
        spec: GNNSpec,
        features: np.ndarray,
        labels: np.ndarray,
        train_mask: np.ndarray,
        *,
        global_batch: int = 1024,
        fanouts: Optional[Sequence[int]] = None,
        seed: int = 0,
        lr: float = 1e-3,
        rebalance: bool = False,
        cache_policy: str = "none",
        cache_budget: int = 0,
        overlap: bool = False,
        prefetch_depth: int = 2,
        codec=None,
        start_step: int = 0,
        injector=None,
    ) -> "MiniBatchTrainer":
        from repro.optim import adam_init

        book = build_vertex_book(graph, vertex_assignment, k)
        fanouts = tuple(fanouts or PAPER_FANOUTS[spec.num_layers])
        train_ids = np.where(train_mask)[0]
        per_worker = [train_ids[book.owner[train_ids] == w] for w in range(k)]
        seeds_per_worker = max(global_batch // k, 1)
        plan = SamplePlan.build(seeds_per_worker, fanouts)
        params = init_params(spec, seed=seed)
        features = features.astype(np.float32)
        store = FeatureStore.build(
            graph, book, policy=cache_policy, budget=cache_budget,
            features=features, seed=seed, codec=codec,
        )
        return cls(
            graph=graph, book=book, spec=spec,
            features=features, labels=labels.astype(np.int32),
            train_vertices_per_worker=per_worker, fanouts=fanouts, plan=plan,
            global_batch=global_batch, params=params,
            opt_state=adam_init(params), seed=seed,
            lr=lr, rebalance=rebalance, store=store,
            overlap=overlap, prefetch_depth=prefetch_depth, codec=codec,
            start_step=start_step, injector=injector,
            _load_ema=np.ones(k), _seed_share=np.full(k, 1.0 / k),
        )

    # ------------------------------------------------------------- pipeline
    @functools.cached_property
    def engine(self) -> PipelineEngine:
        """The step execution engine (gnn/pipeline.py). Serial mode costs no
        threads; overlap mode starts the producer on first use."""
        preparer = BatchPreparer(
            graph=self.graph, book=self.book, store=self.store,
            plan=self.plan, fanouts=self.fanouts, labels=self.labels,
            train_pools=self.train_vertices_per_worker,
            global_batch=self.global_batch, tiled_layout=self._tiled_layout,
            seed=self.seed, injector=self.injector,
            start_step=self.start_step,
        )
        engine = PipelineEngine(
            preparer, overlap=self.overlap, prefetch_depth=self.prefetch_depth)
        if self.rebalance:
            engine.set_seed_share(self._seed_share)
        return engine

    def close(self) -> None:
        """Release the engine's producer/sampler threads (overlap mode)."""
        if "engine" in self.__dict__:
            self.engine.close()

    @property
    def _tiled_layout(self) -> bool:
        return self.spec.agg_backend != "scatter"

    @property
    def _layer_sizes(self) -> list:
        return [p.n_dst for p in self.plan.layers]

    # ------------------------------------------------------------------ step
    @functools.cached_property
    def _train_step(self):
        from repro.optim import adam_update

        spec = self.spec
        lr = self.lr
        sizes = tuple(self._layer_sizes)
        codec = as_codec(self.codec)

        # donate params/opt_state so the device step updates them in place —
        # the trainer never reads the old buffers again (declaration +
        # CPU whitelist live in step_donate_argnums).
        if codec.lossless:
            # historical step graph, untouched (bitwise-identical default)
            def loss_of(params, stacked):
                losses = jax.vmap(
                    lambda batch: minibatch_loss(spec, params, batch, sizes),
                    axis_name=AXIS,
                )(stacked)
                return jnp.mean(losses)

            def step(params, opt_state, stacked):
                loss, grads = jax.value_and_grad(loss_of)(params, stacked)
                new_p, new_s = adam_update(grads, opt_state, params, lr=lr)
                return loss, new_p, new_s

            return jax.jit(step, donate_argnums=step_donate_argnums(True))

        # lossy codec: per-worker grads completed by the error-feedback
        # compressed pmean; the EF residual rides along as a [k, ...] carry
        def per_worker(params, batch, ef):
            loss, grads = jax.value_and_grad(
                lambda p: minibatch_loss(spec, p, batch, sizes))(params)
            mean_grads, new_ef = codec_grad_reduce(codec, grads, ef, AXIS)
            return loss, mean_grads, new_ef

        def step(params, opt_state, stacked, ef):
            losses, grads, new_ef = jax.vmap(
                per_worker, in_axes=(None, 0, 0), axis_name=AXIS,
            )(params, stacked, ef)
            grads = jax.tree.map(lambda g: g[0], grads)  # replica-consistent
            new_p, new_s = adam_update(grads, opt_state, params, lr=lr)
            return jnp.mean(losses), new_p, new_s, new_ef

        return jax.jit(step, donate_argnums=step_donate_argnums(False))

    def _init_ef(self):
        """Per-worker zero EF residuals, stacked [k, ...]."""
        return jax.tree.map(
            lambda p: jnp.zeros((self.book.k,) + p.shape, jnp.float32),
            self.params)

    def set_epoch(self, epoch: int) -> None:
        """Advance epoch-scheduled codecs (VariableRatioCodec) on the
        gradient all-reduce. The feature-store codec is frozen at build time
        — features are layer-0 data, so the schedule's layer-0 tier applies
        to them throughout. Re-jits the step only when the schedule actually
        changes tier."""
        codec = as_codec(self.codec)
        advance = getattr(codec, "at_epoch", None)
        if advance is None:
            return
        new = advance(epoch)
        if (new.ratio(0), new.ratio(1)) != (codec.ratio(0), codec.ratio(1)):
            self.codec = new
            self.__dict__.pop("_train_step", None)
        else:
            self.codec = new

    def train_step(self) -> StepMetrics:
        t0 = time.perf_counter()
        pb, wait = self.engine.next_batch()
        t1 = time.perf_counter()
        if as_codec(self.codec).lossless:
            loss, self.params, self.opt_state = self._train_step(
                self.params, self.opt_state, pb.stacked
            )
        else:
            if self.ef_state is None:
                self.ef_state = self._init_ef()
            loss, self.params, self.opt_state, self.ef_state = (
                self._train_step(self.params, self.opt_state, pb.stacked,
                                 self.ef_state))
        loss = float(loss)  # blocks on the device step
        t2 = time.perf_counter()
        wall = t2 - t0
        # serial mode: phases are contiguous, so charge the (tiny) engine
        # overhead to compute and the four phases sum exactly to the wall
        compute = (t2 - t1) if self.overlap else (wall - pb.host_time)
        tracer = get_tracer()
        if tracer.enabled:
            # the step/compute spans share the StepMetrics timestamps —
            # one clock, whether read from the trace or from the row
            tracer.record_span("minibatch.compute", t1, t2, cat="step",
                               args={"step": pb.index})
            tracer.record_span("minibatch.step", t0, t2, cat="step",
                               args={"step": pb.index, "loss": loss})

        if self.rebalance:
            self._load_ema = (0.7 * self._load_ema
                              + 0.3 * np.maximum(pb.input_vertices, 1))
            inv = 1.0 / self._load_ema
            self._seed_share = inv / inv.sum()
            self.engine.set_seed_share(self._seed_share)

        fetch = pb.fetch_stats
        return StepMetrics(
            loss=loss,
            input_vertices=pb.input_vertices,
            remote_vertices=pb.remote_vertices,
            edges=pb.edges,
            sample_time_host=pb.sample_time,
            compute_time_host=compute,
            cache_hits=np.array([s.num_cache_hit for s in fetch]),
            remote_misses=np.array([s.num_remote_miss for s in fetch]),
            miss_bytes=np.array([s.miss_bytes for s in fetch]),
            wire_bytes=np.array([s.wire_bytes for s in fetch]),
            fetch_time_host=pb.fetch_time,
            transfer_time_host=pb.transfer_time,
            step_wall_host=wall,
            queue_wait_host=wait,
            overlap=self.overlap,
        )
