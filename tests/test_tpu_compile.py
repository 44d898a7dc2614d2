"""Compile rehearsals for one TPU v5e chip, made without the chip.

The TPU compiler is installed with jax, and it compiles for a topology that
is described rather than attached. It refuses what interpret mode accepts:
misaligned blocks, primitives Mosaic cannot lower, programs that do not fit
the chip. Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module-scoped fixture only, never at
import: one process at a time may load the TPU library, and every pytest
worker imports this file.

Also here: the persistent compile cache the entry points switch on.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.launch import compile_cache


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compile cache off: an
    entry written for a chip that is absent cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture()
def kernel_dispatch(monkeypatch):
    """Make `ops` dispatch to the Pallas kernel as it does on a TPU host.
    Traces cached under the CPU dispatch are dropped on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("combiner,f", [("sum", 512), ("max", 512),
                                        ("sum", 4), ("max", 4)])
def test_segment_spmm_compiles_for_v5e(one_chip, kernel_dispatch, combiner,
                                       f):
    """The paper width (F=512, 4 feature tiles) and GAT's 4-head score
    aggregate, with 3 edge blocks per row tile: the grid revisits each
    output block across the reduction axis."""
    n_tiles, blocks_per_tile = 2, 3
    rows = n_tiles * ops.DEFAULT_TILE_V
    e = rows // ops.DEFAULT_TILE_V * blocks_per_tile * ops.DEFAULT_BLOCK_E
    fn = jax.jit(lambda m, d: ops.segment_spmm(m, d, rows, combiner=combiner))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((e, f), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("model,aggregates", [("sage", 1), ("gat", 3)])
def test_fullbatch_step_compiles_for_v5e(one_chip, kernel_dispatch, model,
                                         aggregates):
    """One full-batch train step at the paper width F = H = 512 on the
    tiled backend: every layer's aggregates run the kernel."""
    from repro.core.graph import paper_graph
    from repro.gnn.fullbatch import FullBatchTrainer
    from repro.gnn.models import GNNSpec

    g = paper_graph("OR", scale=0.01, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 512)).astype(np.float32)
    labels = rng.integers(0, 16, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    spec = GNNSpec(model=model, feature_dim=512, hidden_dim=512,
                   num_classes=16, num_layers=3, agg_backend="tiled")
    tr = FullBatchTrainer.build(g, np.zeros(g.num_edges, np.int32), 1, spec,
                                feats, labels, train)
    compiled = tr._train_step.lower(
        *_shapes((tr.params, tr.opt_state, tr.blocks), one_chip)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == aggregates * spec.num_layers


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it and the entry
    points change nothing."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    """Without the variable the cache sits at one fixed path inside the
    checkout, the same on every call."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        first = compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert compile_cache.use_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert first == os.path.join(checkout, ".jax_cache")
