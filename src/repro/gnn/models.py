"""GNN models (GraphSAGE / GCN / GAT) as per-device JAX functions.

Every function here operates on ONE partition's local block:

  x      [Vloc+1, F]   local vertex states (last row = dummy/padding sink)
  esrc   [Eloc]        local src indices (pad -> dummy row)
  edst   [Eloc]        local dst indices
  degree [Vloc+1]      *global* symmetric degree of each local vertex
  master [Vloc+1]      bool, true where this partition owns the vertex

plus a `sync` strategy (repro.gnn.sync.SyncStrategy). Every edge aggregation
goes through `sync.edge_aggregate(blk, payload, msg_fn, ...)`, which returns
the COMPLETE global per-destination reduce regardless of how features are
laid out or moved — partial-aggregate completion (Local/Dense/Halo over an
`EdgePartitionBook`) or ring-pipelined block rotation (RingSync over a
`BlockRowBook`). With the `LocalSync` no-op the same code is the exact
single-machine model — that equivalence is the core system invariant and is
tested (distributed forward == single-device forward, allclose, for every
strategy).

Self terms (GCN's self-loop, GAT's self-edge) are added AFTER completion,
ungated: completed aggregates and x are replica-consistent, so the term is
counted exactly once per vertex under every strategy — including ring,
where no replicas exist at all.

Aggregation is over the symmetrised adjacency: each stored edge (u, v)
produces messages u->v and v->u (DGL-on-undirected semantics, which both
DistGNN and the paper's DistDGL setup use).

Models follow the paper's setup (§4.1/§5.1): GraphSAGE (mean), GCN, GAT.

Aggregation backend (`GNNSpec.agg_backend`): every edge aggregation goes
through `kernels.ops` — the sums through `aggregate`, GAT's attention
through `edge_softmax` (one edge pass: row max, exp-sum and weighted z
together) — which dispatch on the knob —
  scatter — data-dependent `at[].add` / `at[].max` (the oracle)
  tiled   — pre-sorted/pre-blocked layout (`Block.agg_order`/`agg_ldst`,
            built by the partition book) through the tiled segment-reduce:
            jnp oracle off-TPU, the Pallas one-hot kernel on TPU (its sum
            or softmax combiner). Backward of the sum is a plain gather,
            that of the softmax a recompute from the saved row max
            (custom_vjp; one reverse kernel pass over the layout where
            the edge list is its own transpose), so gradients match the
            scatter oracle to allclose, and the forward's O(E) edge hot
            path of every model is scatter-free under tiled/pallas. The
            backward is scatter-free too where the list is its own
            transpose. (The k-way replica sync
            still scatters into its bucket-sized halo buffers —
            O(replicas), the network path, not the edge hot path.)
  pallas  — like tiled but forces the Pallas kernel (interpreted on CPU).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

Params = Any


@dataclasses.dataclass(frozen=True)
class GNNSpec:
    model: str = "sage"          # sage | gcn | gat
    feature_dim: int = 64
    hidden_dim: int = 64
    num_classes: int = 16
    num_layers: int = 2
    gat_heads: int = 4
    agg_backend: str = "scatter"  # scatter | tiled | pallas (ops.aggregate)

    def dims(self) -> list[tuple[int, int]]:
        ins = [self.feature_dim] + [self.hidden_dim] * (self.num_layers - 1)
        outs = [self.hidden_dim] * (self.num_layers - 1) + [self.num_classes]
        return list(zip(ins, outs))

    def aggregate_dims(self, mode: str = "halo") -> list[list[int]]:
        """Per layer, the wire width of every `sync.edge_aggregate` the
        layer issues, in issue order — the dims that actually cross the
        network, which depend on WHAT the strategy ships:

          halo/dense/local complete partial AGGREGATES (message rows):
            sage/gcn  [d_in]                (msg = masked src features)
            gat       [H, H, H·dh]          (the edge softmax's row max,
                                             exp-sum and weighted z)
          ring rotates the PAYLOAD itself:
            sage/gcn  [d_in]                (payload == message width)
            gat       [H·dh]                (z; each stage's softmax
                                             partial merges locally)

        The byte accountants (`FullBatchTrainer.*_bytes_per_epoch`,
        `LayerwiseInference.sync_bytes`) and the runtime reconciliation
        gate sum `sync_*bytes_per_round` over exactly these widths, which
        is what makes measured-vs-model byte checks exact.
        """
        out = []
        for din, dout in self.dims():
            if self.model == "gat":
                h = self.gat_heads
                dh = max(dout // h, 1)
                if mode == "ring":
                    out.append([h * dh])
                else:
                    out.append([h, h, h * dh])
            else:
                out.append([din])
        return out


def _glorot(rng: np.random.Generator, shape: tuple[int, ...]) -> jnp.ndarray:
    fan_in, fan_out = shape[0], shape[-1]
    limit = float(np.sqrt(6.0 / (fan_in + fan_out)))
    return jnp.asarray(rng.uniform(-limit, limit, size=shape), dtype=jnp.float32)


def init_params(spec: GNNSpec, seed: int = 0) -> Params:
    rng = np.random.default_rng(seed)
    layers = []
    for li, (din, dout) in enumerate(spec.dims()):
        if spec.model == "sage":
            layers.append({
                "w_self": _glorot(rng, (din, dout)),
                "w_neigh": _glorot(rng, (din, dout)),
                "b": jnp.zeros((dout,), jnp.float32),
            })
        elif spec.model == "gcn":
            layers.append({
                "w": _glorot(rng, (din, dout)),
                "b": jnp.zeros((dout,), jnp.float32),
            })
        elif spec.model == "gat":
            h = spec.gat_heads
            dh = max(dout // h, 1)
            layers.append({
                "w": _glorot(rng, (din, h * dh)),
                "a_src": _glorot(rng, (h, dh)),
                "a_dst": _glorot(rng, (h, dh)),
                "b": jnp.zeros((h * dh,), jnp.float32),
                "w_out": (_glorot(rng, (h * dh, dout))
                          if h * dh != dout else jnp.eye(h * dh, dtype=jnp.float32)),
            })
        else:
            raise ValueError(f"unknown model {spec.model!r}")
    return {"layers": layers}


# ---------------------------------------------------------------------------
# Aggregation primitives (local partials; `sync` completes them globally)
# ---------------------------------------------------------------------------


def sage_layer(p, x, blk, sync, *, final: bool,
               backend: str = "scatter") -> jnp.ndarray:
    agg = sync.edge_aggregate(
        blk, x, lambda src, dst, mask: src * mask[:, None], backend=backend)
    mean = agg / jnp.maximum(blk.degree, 1.0)[:, None]
    h = x @ p["w_self"] + mean @ p["w_neigh"] + p["b"]
    return h if final else jax.nn.relu(h)


def gcn_layer(p, x, blk, sync, *, final: bool,
              backend: str = "scatter") -> jnp.ndarray:
    dnorm = 1.0 / jnp.sqrt(blk.degree + 1.0)  # self-loop-augmented degree
    agg = sync.edge_aggregate(
        blk, x * dnorm[:, None],
        lambda src, dst, mask: src * mask[:, None], backend=backend)
    # Self-loop term after completion: the completed aggregate and x are
    # replica-consistent, so no master gating is needed.
    agg = agg + x * (dnorm * dnorm)[:, None]
    h = (agg * dnorm[:, None]) @ p["w"] + p["b"]
    return h if final else jax.nn.relu(h)


def gat_layer(p, x, blk, sync, *, final: bool,
              backend: str = "scatter") -> jnp.ndarray:
    n = x.shape[0]
    h_heads, dh = p["a_src"].shape
    z = x @ p["w"]                                   # [n, H*dh]
    s_src = ops.attention_scores(z, p["a_src"])      # [n, H]
    s_dst = ops.attention_scores(z, p["a_dst"])
    # one edge pass: the complete (max, exp-sum, weighted z) over the
    # neighbours, which the sync rescales to the global max before summing
    # partials (ring: merges stage by stage). The payload is z alone; each
    # edge's source score is a_src . z_src, so a lossy wire codec hands the
    # weight and the message the same decoded row.
    agg = sync.edge_aggregate(blk, z, (p["a_src"], s_dst), reduce="softmax",
                              backend=backend)
    # the self edge, merged after completion, ungated: completed aggregates
    # are replica-consistent
    e_self = jax.nn.leaky_relu(s_src + s_dst, 0.2)
    _, den, num = ops.softmax_merge(agg, ops.softmax_edge(e_self, z))
    # den >= 1: the row's largest score contributes exp(0)
    out = (num.reshape(n, h_heads, dh) / den[:, :, None]).reshape(
        n, h_heads * dh) + p["b"]
    out = out @ p["w_out"]
    return out if final else jax.nn.elu(out)


_LAYERS = {"sage": sage_layer, "gcn": gcn_layer, "gat": gat_layer}


def forward(spec: GNNSpec, params: Params, x, blk, sync) -> jnp.ndarray:
    """Full model forward on one partition's block. Returns logits
    [Vloc+1, num_classes] (valid at every replica; loss is master-gated)."""
    layer_fn = _LAYERS[spec.model]
    h = x
    # aggregate ordinals restart per forward pass (VariableRatioCodec ramps
    # its compression ratio on them; a no-op for fixed-ratio codecs)
    reset = getattr(sync, "reset_layer_counter", None)
    if reset is not None:
        reset()
    n_layers = len(params["layers"])
    for li, p in enumerate(params["layers"]):
        h = layer_fn(p, h, blk, sync, final=(li == n_layers - 1),
                     backend=spec.agg_backend)
        # dummy row must stay zero: it is a scatter sink for padding
        h = h.at[-1].set(0.0)
    return h


def loss_fn(spec: GNNSpec, params: Params, x, blk, sync) -> jnp.ndarray:
    """Masked softmax cross-entropy, averaged over global training vertices.

    Loss is counted only at master replicas (each training vertex counted
    exactly once across the cluster).
    """
    logits = forward(spec, params, x, blk, sync)
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.maximum(blk.labels, 0)
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    weight = (blk.train_mask & blk.master & (blk.labels >= 0)).astype(jnp.float32)
    local_sum = -(picked * weight).sum()
    local_cnt = weight.sum()
    total = sync.psum(jnp.stack([local_sum, local_cnt]))
    return total[0] / jnp.maximum(total[1], 1.0)
