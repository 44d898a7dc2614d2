"""Representative programs the static analyzer runs its rules over.

A `Program` is one (entry point x configuration) cell plus the invariants
the rules should hold it to. Four kinds:

  jaxpr     `make()` returns a list of traced `ClosedJaxpr`s (nothing
            compiles). Walked by the no-scatter and dtype-policy rules.
  hlo       `make()` returns compiled HLO text. Needs `devices` forced
            host devices (the rule skips with an info finding when the
            process has fewer). Checked by the collective-budget rule
            against `budget()` — the prediction from
            `repro.gnn.sync.collective_budget`.
  donation  declared vs expected `donate_argnums`, plus (optionally) a
            compiled probe whose `input_output_alias` header must agree.
  retrace   `sweep()` builds a FRESH trainer/engine and drives a few
            steps. The retrace-guard rule runs it twice — the first run
            warms the process-wide eager-dispatch caches — and counts
            backend compiles during the second against `retrace_budget`.

The default grid covers the paper's axes: {sage, gat} models x
{scatter, tiled, pallas} aggregation backends x {halo, ring, dense, local}
sync strategies x {fp32, int8, variable} wire codecs, over full-batch
training, mini-batch training, layer-wise inference and online serving.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import numpy as np

from repro.kernels.ops import scatter_free_traced

D = 8                 # feature/hidden width of every analysis program
K = 4                 # partitions for the distributed cells
NUM_CLASSES = 4

__all__ = ["Program", "build_programs", "violation_program", "GRIDS"]


@dataclasses.dataclass
class Program:
    """One analyzed program + the invariants rules hold it to."""

    name: str
    kind: str                                  # jaxpr | hlo | donation | retrace
    make: Optional[Callable[[], Any]] = None   # artifact builder (lazy)
    meta: dict = dataclasses.field(default_factory=dict)
    # --- no-scatter rule (jaxpr) -------------------------------------------
    # True: scatter-add/max must NOT appear; False: it MUST (anchor cell
    # proving the rule still sees scatters); None: report only.
    expect_scatter_free: Optional[bool] = None
    # --- dtype-policy rule (jaxpr): codec governing allowed narrow dtypes --
    codec: Optional[str] = None
    # --- collective-budget rule (hlo) --------------------------------------
    budget: Optional[Callable[[], dict]] = None
    devices: int = 1
    # --- donation rule ------------------------------------------------------
    declared_donate: Optional[Callable[[], tuple]] = None
    expected_donate: Optional[Callable[[], tuple]] = None
    expect_alias: Optional[bool] = None        # probe HLO must carry aliases
    # --- retrace-guard rule --------------------------------------------------
    sweep: Optional[Callable[[], None]] = None
    retrace_budget: Optional[int] = None


# ---------------------------------------------------------------------------
# Shared fixture (one small paper graph, cached per process)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _fixture():
    from repro.core.graph import paper_graph

    g = paper_graph("OR", scale=0.01, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, D)).astype(np.float32)
    labels = rng.integers(0, NUM_CLASSES, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    return g, feats, labels, train


@functools.lru_cache(maxsize=None)
def _assignment(k: int):
    from repro.core.edge_partition import partition_edges

    return partition_edges(_fixture()[0], k, "hdrf", seed=1)


def _spec(model: str, backend: str):
    from repro.gnn.models import GNNSpec

    return GNNSpec(model=model, feature_dim=D, hidden_dim=D,
                   num_classes=NUM_CLASSES, agg_backend=backend)


@functools.lru_cache(maxsize=None)
def _book_blocks(sync_mode: str, tiled: bool, k: int):
    from repro.gnn.fullbatch import build_book, build_device_blocks

    g, feats, labels, train = _fixture()
    if sync_mode == "ring":
        a = None
    elif k == 1:
        a = np.zeros(g.num_edges, np.int64)
    else:
        a = _assignment(k)
    book = build_book(g, a, k, sync_mode=sync_mode, tiled_layout=tiled)
    return book, build_device_blocks(book, feats, labels, train)


# ---------------------------------------------------------------------------
# jaxpr builders (trace only — run on any device count)
# ---------------------------------------------------------------------------


def _fullbatch_jaxpr(model: str, backend: str, sync_mode: str,
                     codec: Optional[str], k: int = K) -> list:
    import jax

    from repro.gnn import models
    from repro.gnn.fullbatch import make_step_fns, wrap_spmd

    spec = _spec(model, backend)
    book, blocks = _book_blocks(sync_mode, backend != "scatter", k)
    loss, _ = make_step_fns(spec, sync_mode, book.num_vertices, k,
                            codec=codec)
    wrapped = wrap_spmd(loss, k, "sim")
    params = models.init_params(spec, seed=0)
    return [jax.make_jaxpr(wrapped)(params, blocks)]


def _minibatch_jaxpr(model: str, backend: str,
                     codec: Optional[str] = None) -> list:
    import jax

    from repro.gnn.minibatch import MiniBatchTrainer, minibatch_loss

    g, feats, labels, train = _fixture()
    spec = _spec(model, backend)
    tr = MiniBatchTrainer.build(
        g, np.zeros(g.num_vertices, np.int64), 1, spec, feats, labels,
        train, global_batch=64, fanouts=(4, 4), seed=0, codec=codec,
    )
    pb = tr.engine.preparer.prepare()
    batch0 = jax.tree.map(lambda a: a[0], pb.stacked)
    sizes = tuple(tr._layer_sizes)

    def fn(p, b):
        return minibatch_loss(spec, p, b, sizes, axis=None)

    return [jax.make_jaxpr(fn)(tr.params, batch0)]


def _serving_jaxpr(model: str, backend: str) -> list:
    import jax

    from repro.core.partition_book import build_vertex_book
    from repro.gnn import models
    from repro.gnn.minibatch import mfg_forward
    from repro.serve.engine import build_serving

    g, feats, labels, train = _fixture()
    spec = _spec(model, backend)
    params = models.init_params(spec, seed=0)
    vbook = build_vertex_book(g, np.zeros(g.num_vertices, np.int64), 1)
    embeddings = [
        np.zeros((g.num_vertices, dout), np.float32)
        for _, dout in spec.dims()
    ]
    engines, batchers, _ = build_serving(
        g, vbook, spec, params, embeddings, hops=1, fanout=4, max_batch=8,
    )
    eng, bat = engines[0], batchers[0]
    batch = bat.build_mfg(np.arange(4, dtype=np.int64))
    x = np.zeros((batch.input_ids.shape[0], eng.store.row_dim), np.float32)
    dev = eng.device_batch(batch, x)
    sizes, lp = eng._sizes, eng._layer_params

    def fn(p, b):
        return mfg_forward(spec, p, b, sizes)

    return [jax.make_jaxpr(fn)(lp, dev)]


def _inference_jaxprs(model: str, backend: str, k: int = K) -> list:
    from repro.gnn import models
    from repro.gnn.inference import LayerwiseInference

    g, feats, labels, train = _fixture()
    spec = _spec(model, backend)
    params = models.init_params(spec, seed=0)
    a = (_assignment(k) if k > 1
         else np.zeros(g.num_edges, np.int64))
    eng = LayerwiseInference.build(g, a, k, spec, params, feats,
                                   sync_mode="halo")
    return eng.layer_jaxprs()


# ---------------------------------------------------------------------------
# hlo builders (compile one aggregate under shard_map — need K devices)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _ring_fixture():
    from repro.core.partition_book import build_blockrow_book
    from repro.gnn.sync import build_ring_blocks

    g, feats, _, _ = _fixture()
    zeros = np.zeros(g.num_vertices, np.int32)
    book = build_blockrow_book(g, K)
    blocks = build_ring_blocks(book, feats, zeros, zeros.astype(bool))
    return book, blocks


@functools.lru_cache(maxsize=1)
def _halo_fixture():
    from repro.core.partition_book import build_edge_book
    from repro.gnn.sync import build_blocks

    g, feats, _, _ = _fixture()
    zeros = np.zeros(g.num_vertices, np.int32)
    book = build_edge_book(g, _assignment(K), K)
    blocks = build_blocks(book, feats, zeros, zeros.astype(bool))
    return book, blocks


def _ring_hlo(codec: Optional[str]) -> str:
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.gnn.sync import RingSync
    from repro.launch.mesh import make_mesh

    _, blocks = _ring_fixture()
    mesh = make_mesh((K,), ("parts",))

    def per_device(blocks_local):
        blk = jax.tree.map(lambda a: a[0], blocks_local)
        sync = RingSync(axis="parts", k=K, codec=codec)
        h = sync.edge_aggregate(blk, blk.x, lambda s, dst, m: s * m[:, None])
        return h[None]

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(P("parts"),),
                       out_specs=P("parts"), check_vma=False)
    return jax.jit(fn).lower(blocks).compile().as_text()


def _partial_agg_hlo(mode: str, codec: Optional[str]) -> str:
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.gnn.sync import make_sync
    from repro.launch.mesh import make_mesh

    book, blocks = _halo_fixture()
    mesh = make_mesh((K,), ("parts",))

    def per_device(blocks_local):
        blk = jax.tree.map(lambda a: a[0], blocks_local)
        sync = make_sync(mode, blk, book.num_vertices, "parts", codec=codec)
        h = sync.broadcast(sync.reduce_sum(blk.x))   # one reduce+broadcast
        return jax.tree.map(lambda a: a[None], h)

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=(P("parts"),),
                       out_specs=P("parts"), check_vma=False)
    return jax.jit(fn).lower(blocks).compile().as_text()


def _sync_budget(mode: str, codec: Optional[str]) -> dict:
    from repro.gnn.sync import collective_budget

    book = (_ring_fixture() if mode == "ring" else _halo_fixture())[0]
    return collective_budget(book, D, mode, codec=codec)


# ---------------------------------------------------------------------------
# donation + retrace builders
# ---------------------------------------------------------------------------


def _donation_probe_hlo() -> str:
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    return fn.lower(jnp.zeros((8,), jnp.float32)).compile().as_text()


def _fresh_fullbatch(codec: Optional[str]):
    from repro.gnn.fullbatch import FullBatchTrainer

    g, feats, labels, train = _fixture()
    return FullBatchTrainer.build(
        g, np.zeros(g.num_edges, np.int64), 1, _spec("sage", "scatter"),
        feats, labels, train, seed=0, codec=codec,
    )


def _sweep_fullbatch_fp32():
    tr = _fresh_fullbatch(None)

    def hot():
        for _ in range(3):
            tr.train_step()

    return hot


def _sweep_fullbatch_variable():
    tr = _fresh_fullbatch("variable")

    def hot():
        for epoch in range(4):
            tr.set_epoch(epoch)
            tr.train_step()

    return hot


def _sweep_minibatch_variable():
    from repro.gnn.minibatch import MiniBatchTrainer

    g, feats, labels, train = _fixture()
    tr = MiniBatchTrainer.build(
        g, np.zeros(g.num_vertices, np.int64), 1, _spec("sage", "scatter"),
        feats, labels, train, global_batch=64, fanouts=(4, 4), seed=0,
        codec="variable",
    )

    def hot():
        for epoch in range(4):
            tr.set_epoch(epoch)
            tr.train_step()

    return hot


# serving retrace: `_compiled_step` is an lru_cache over (spec, hops, plan),
# so each sweep must present a spec the process has never served — otherwise
# the warm run would leave nothing to compile and the guard would measure 0.
_SERVE_SPIN = {"n": 0}


def _sweep_serving():
    from repro.core.partition_book import build_vertex_book
    from repro.gnn import models
    from repro.serve.engine import build_serving

    _SERVE_SPIN["n"] += 1
    g, feats, labels, train = _fixture()
    spec = dataclasses.replace(
        _spec("sage", "scatter"), num_classes=NUM_CLASSES + _SERVE_SPIN["n"])
    params = models.init_params(spec, seed=0)
    vbook = build_vertex_book(g, np.zeros(g.num_vertices, np.int64), 1)
    embeddings = [
        np.zeros((g.num_vertices, dout), np.float32)
        for _, dout in spec.dims()
    ]
    engines, batchers, _ = build_serving(
        g, vbook, spec, params, embeddings, hops=1, fanout=4, max_batch=8,
    )

    def hot():
        for ids in (np.arange(4, dtype=np.int64),
                    np.arange(4, 10, dtype=np.int64)):
            engines[0].answer(batchers[0].build_mfg(ids))

    return hot


# ---------------------------------------------------------------------------
# Grid assembly
# ---------------------------------------------------------------------------

MODELS = ("sage", "gat")
BACKENDS = ("scatter", "tiled")
SYNCS = ("halo", "ring")
WIRE_CODECS = ("fp32", "int8")

GRIDS = ("tiny", "smoke")


def _expect_free(backend: str, sync_mode: str, k: int) -> bool:
    """A traced program is scatter-free iff the aggregation backend avoids
    scatter AND the sync strategy does (halo/dense bucket-scatter at k>1)."""
    return scatter_free_traced(backend) and (sync_mode == "ring" or k == 1)


def _fullbatch_program(model, backend, sync_mode, codec, k=K) -> Program:
    name = f"fullbatch/{model}-{backend}-{sync_mode}-{codec or 'fp32'}-k{k}"
    return Program(
        name=name, kind="jaxpr",
        make=functools.partial(_fullbatch_jaxpr, model, backend, sync_mode,
                               codec, k),
        meta={"entry": "fullbatch", "model": model, "backend": backend,
              "sync": sync_mode, "k": k},
        expect_scatter_free=_expect_free(backend, sync_mode, k),
        codec=codec or "fp32",
    )


def _jaxpr_grid() -> list:
    progs = [
        _fullbatch_program(model, backend, sync_mode, codec)
        for model in MODELS
        for backend in BACKENDS
        for sync_mode in SYNCS
        for codec in WIRE_CODECS
    ]
    # pallas backend: scatter-free by construction on every platform — the
    # green cells proving the no-scatter rule passes real programs (plus the
    # k=1 hot paths the old tests/test_aggregate.py pins covered)
    progs += [
        _fullbatch_program("gat", "pallas", "ring", "fp32"),
        _fullbatch_program("sage", "pallas", "local", "fp32", k=1),
        _fullbatch_program("gat", "pallas", "local", "fp32", k=1),
        # anchor: the scatter oracle MUST trip the walker
        _fullbatch_program("gat", "scatter", "local", "fp32", k=1),
    ]
    progs += [
        Program(
            name="minibatch/gat-pallas-fp32",
            kind="jaxpr", make=functools.partial(_minibatch_jaxpr, "gat",
                                                 "pallas"),
            meta={"entry": "minibatch", "model": "gat", "backend": "pallas"},
            expect_scatter_free=True, codec="fp32",
        ),
        Program(
            name="minibatch/gat-scatter-fp32",
            kind="jaxpr", make=functools.partial(_minibatch_jaxpr, "gat",
                                                 "scatter"),
            meta={"entry": "minibatch", "model": "gat", "backend": "scatter"},
            expect_scatter_free=False, codec="fp32",
        ),
        Program(
            name="minibatch/sage-tiled-fp32",
            kind="jaxpr", make=functools.partial(_minibatch_jaxpr, "sage",
                                                 "tiled"),
            meta={"entry": "minibatch", "model": "sage", "backend": "tiled"},
            expect_scatter_free=scatter_free_traced("tiled"), codec="fp32",
        ),
        Program(
            name="serving/sage-pallas-fp32",
            kind="jaxpr", make=functools.partial(_serving_jaxpr, "sage",
                                                 "pallas"),
            meta={"entry": "serving", "model": "sage", "backend": "pallas"},
            expect_scatter_free=True, codec="fp32",
        ),
        Program(
            name="serving/gat-scatter-fp32",
            kind="jaxpr", make=functools.partial(_serving_jaxpr, "gat",
                                                 "scatter"),
            meta={"entry": "serving", "model": "gat", "backend": "scatter"},
            expect_scatter_free=False, codec="fp32",
        ),
        Program(
            name="inference/sage-tiled-halo-k4",
            kind="jaxpr", make=functools.partial(_inference_jaxprs, "sage",
                                                 "tiled", K),
            meta={"entry": "inference", "model": "sage", "backend": "tiled",
                  "sync": "halo", "k": K},
            expect_scatter_free=_expect_free("tiled", "halo", K),
            codec="fp32",
        ),
        Program(
            name="inference/gat-pallas-local-k1",
            kind="jaxpr", make=functools.partial(_inference_jaxprs, "gat",
                                                 "pallas", 1),
            meta={"entry": "inference", "model": "gat", "backend": "pallas",
                  "sync": "local", "k": 1},
            expect_scatter_free=True, codec="fp32",
        ),
    ]
    return progs


def _hlo_grid() -> list:
    cells = [
        ("ring", "fp32"), ("ring", "int8"),
        ("halo", "fp32"), ("halo", "int8"),
        ("dense", "fp32"),
    ]
    progs = []
    for mode, codec in cells:
        make = (functools.partial(_ring_hlo, codec) if mode == "ring"
                else functools.partial(_partial_agg_hlo, mode, codec))
        progs.append(Program(
            name=f"hlo/{mode}-{codec}", kind="hlo", make=make,
            meta={"entry": "sync-aggregate", "sync": mode},
            budget=functools.partial(_sync_budget, mode, codec),
            devices=K, codec=codec,
        ))
    return progs


def _donation_programs() -> list:
    def fb(lossless):
        from repro.gnn.fullbatch import step_donate_argnums
        return step_donate_argnums(lossless)

    def mb(lossless):
        from repro.gnn.minibatch import step_donate_argnums
        return step_donate_argnums(lossless)

    def policy(lossless, trainer):
        # the donation contract: every trainer donates its (params/opt or
        # blocks/ef) carries off-CPU and declares () on XLA:CPU, which
        # cannot alias and would warn once per compile otherwise
        import jax
        if jax.default_backend() == "cpu":
            return ()
        if trainer == "fullbatch":
            return () if lossless else (1, 3)
        return (0, 1) if lossless else (1, 3)

    return [
        Program(
            name="donation/jit-probe", kind="donation",
            make=_donation_probe_hlo, expect_alias=True,
            meta={"entry": "probe"},
            declared_donate=lambda: (0,), expected_donate=lambda: (0,),
        ),
        Program(
            name="donation/fullbatch-lossy", kind="donation",
            meta={"entry": "fullbatch"},
            declared_donate=functools.partial(fb, False),
            expected_donate=functools.partial(policy, False, "fullbatch"),
        ),
        Program(
            name="donation/minibatch-lossless", kind="donation",
            meta={"entry": "minibatch"},
            declared_donate=functools.partial(mb, True),
            expected_donate=functools.partial(policy, True, "minibatch"),
        ),
        Program(
            name="donation/minibatch-lossy", kind="donation",
            meta={"entry": "minibatch"},
            declared_donate=functools.partial(mb, False),
            expected_donate=functools.partial(policy, False, "minibatch"),
        ),
    ]


def _retrace_programs() -> list:
    return [
        Program(
            name="retrace/fullbatch-fp32", kind="retrace",
            sweep=_sweep_fullbatch_fp32, retrace_budget=1,
            meta={"entry": "fullbatch", "steps": 3},
        ),
        Program(
            name="retrace/fullbatch-variable", kind="retrace",
            # the epoch schedule changes wire tier once (int8 -> bf16 at
            # epoch 2), so exactly one EXTRA jit is the budget
            sweep=_sweep_fullbatch_variable, retrace_budget=2,
            meta={"entry": "fullbatch", "epochs": 4, "codec": "variable"},
        ),
        Program(
            name="retrace/minibatch-variable", kind="retrace",
            sweep=_sweep_minibatch_variable, retrace_budget=2,
            meta={"entry": "minibatch", "epochs": 4, "codec": "variable"},
        ),
        Program(
            name="retrace/serving", kind="retrace",
            # 1 jitted serve step + 1 eager result-slice compile on the
            # sweep's unique logits width; the second answer must hit both
            sweep=_sweep_serving, retrace_budget=2,
            meta={"entry": "serving", "answers": 2},
        ),
    ]


def build_programs(grid: str = "smoke") -> list:
    """The program set for a grid tier.

    tiny   a fast cross-section (seconds): one green + one anchor jaxpr
           cell per entry point, the donation policy checks, no compiles.
    smoke  the full CI gate: every jaxpr grid cell, the five compiled
           sync-aggregate HLO cells, donation probes and retrace sweeps.
    """
    if grid not in GRIDS:
        raise ValueError(f"unknown grid {grid!r}; choose from {GRIDS}")
    if grid == "tiny":
        return [
            _fullbatch_program("sage", "pallas", "ring", "int8"),
            _fullbatch_program("gat", "scatter", "local", "fp32", k=1),
            Program(
                name="minibatch/gat-pallas-fp32",
                kind="jaxpr",
                make=functools.partial(_minibatch_jaxpr, "gat", "pallas"),
                meta={"entry": "minibatch"},
                expect_scatter_free=True, codec="fp32",
            ),
        ] + _donation_programs()[1:]          # policy checks only, no probe
    return (_jaxpr_grid() + _hlo_grid() + _donation_programs()
            + _retrace_programs())


# ---------------------------------------------------------------------------
# Seeded violations (--inject-violation): prove each rule can fail
# ---------------------------------------------------------------------------


def _scatter_violation_jaxpr() -> list:
    import jax
    import jax.numpy as jnp

    def bad(h):
        return jnp.zeros((16, D)).at[jnp.arange(8)].add(h)

    return [jax.make_jaxpr(bad)(jnp.zeros((8, D)))]


def _dtype_violation_jaxpr() -> list:
    import jax
    import jax.numpy as jnp

    def bad(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    return [jax.make_jaxpr(bad)(jnp.zeros((8, D)))]


_BUDGET_VIOLATION_HLO = """
  %ar = f32[1024,8]{1,0} all-reduce(f32[1024,8]{1,0} %x), replica_groups={}
  %cp = f32[64]{0} collective-permute(f32[64]{0} %y), source_target_pairs={{0,1}}
"""


def _retrace_violation_sweep() -> None:
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: x * 2.0)
    for n in (4, 8, 16):        # shape-dependent: one compile per shape
        step(jnp.zeros((n,), jnp.float32))


def violation_program(rule: str) -> Program:
    """A program deliberately violating `rule` — the CLI's
    --inject-violation hook, proving the gate exits non-zero."""
    if rule == "no-scatter":
        return Program(
            name="injected/no-scatter", kind="jaxpr",
            make=_scatter_violation_jaxpr, expect_scatter_free=True,
            meta={"injected": True},
        )
    if rule == "dtype-policy":
        return Program(
            name="injected/dtype-policy", kind="jaxpr",
            make=_dtype_violation_jaxpr, codec="fp32",
            meta={"injected": True},
        )
    if rule == "collective-budget":
        return Program(
            name="injected/collective-budget", kind="hlo",
            make=lambda: _BUDGET_VIOLATION_HLO, devices=1,
            budget=lambda: {"all-reduce": {"count": (1, 1),
                                           "cluster_bytes": 64}},
            meta={"injected": True},
        )
    if rule == "donation":
        return Program(
            name="injected/donation", kind="donation",
            declared_donate=lambda: (0, 1), expected_donate=lambda: (),
            meta={"injected": True},
        )
    if rule == "retrace-guard":
        return Program(
            name="injected/retrace-guard", kind="retrace",
            sweep=_retrace_violation_sweep, retrace_budget=1,
            meta={"injected": True},
        )
    raise ValueError(f"no seeded violation for rule {rule!r}")
