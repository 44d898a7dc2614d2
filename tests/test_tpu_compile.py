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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.hlo import op_names_from_hlo
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
    """The paper width (F=512, 4 feature tiles) and a 4 wide aggregate on
    a ragged layout of 3 row tiles in 6 edge blocks: the
    scalar-prefetched block_tile map picks each block's output block, and
    the grid revisits it across a tile's consecutive blocks."""
    n_tiles, n_blocks = 3, 6
    rows = n_tiles * ops.DEFAULT_TILE_V
    e = n_blocks * ops.DEFAULT_BLOCK_E
    fn = jax.jit(lambda m, d, t: ops.segment_spmm(
        m, d, rows, block_tile=t, combiner=combiner))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((e, f), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((n_blocks,), jnp.int32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# per model: the kernel calls of each layer, the scopes the forward pass
# keeps (`jvp(...)`) and those its backward keeps (`transpose(jvp(...))`)
STEP_CALLS = {
    "sage": (("segment_spmm_sum",), ("edge.gather", "edge.reorder",
                                     "edge.reduce"),
             ("edge.gather", "edge.reduce")),
    "gat": (("segment_spmm_softmax", "segment_spmm_softmax_bwd"),
            ("edge.reorder", "edge.softmax"), ("edge.softmax",)),
}


def _fullbatch_step(model, scale, one_chip):
    """The full-batch train step at the paper width F = H = 512 on the
    tiled backend over OR at `scale`, k = 1, compiled for one v5e."""
    from repro.core.graph import paper_graph
    from repro.gnn.fullbatch import FullBatchTrainer
    from repro.gnn.models import GNNSpec

    g = paper_graph("OR", scale=scale, seed=0)
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 512)).astype(np.float32)
    labels = rng.integers(0, 16, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.3
    spec = GNNSpec(model=model, feature_dim=512, hidden_dim=512,
                   num_classes=16, num_layers=3, agg_backend="tiled")
    tr = FullBatchTrainer.build(g, np.zeros(g.num_edges, np.int32), 1, spec,
                                feats, labels, train)
    return g, tr._train_step.lower(
        *_shapes((tr.params, tr.opt_state, tr.blocks), one_chip)).compile()


def _kernel_calls(hlo: str) -> list:
    return sorted(re.findall(
        r"%(segment_spmm_(?:sum|max|softmax|softmax_bwd))\.\d+ = "
        r".*?custom-call\(", hlo))


def _softmax_backward_scatters(hlo: str) -> list:
    """op_names of the scatters in the edge softmax's backward."""
    return [n for n in op_names_from_hlo(hlo).values()
            if "transpose(jvp(edge.softmax))" in n and "scatter" in n]


@pytest.mark.parametrize("model,aggregates", [("sage", 1), ("gat", 3)])
def test_fullbatch_step_compiles_for_v5e(one_chip, kernel_dispatch, model,
                                         aggregates):
    """One full-batch train step: each layer makes one forward kernel
    call, which the compiled step names by combiner (sage sums, GAT's edge
    softmax computes the layer's three aggregates, row max, exp-sum and
    weighted z, in one pass), GAT's backward one reverse pass a layer and
    no scatter, and the edge path's scopes survive the TPU compiler's
    fusions."""
    from repro.gnn.models import GNNSpec

    spec = GNNSpec(model=model, feature_dim=512, hidden_dim=512,
                   num_classes=16, num_layers=3)
    assert [len(d) for d in spec.aggregate_dims()] == [aggregates] * 3
    _, compiled = _fullbatch_step(model, 0.01, one_chip)
    hlo = compiled.as_text()
    calls, forward, backward = STEP_CALLS[model]
    assert hlo.count("tpu_custom_call") == len(calls) * spec.num_layers
    assert _kernel_calls(hlo) == sorted(calls * 3)
    assert not _softmax_backward_scatters(hlo)
    names = set(op_names_from_hlo(hlo).values())
    for scope in forward:
        assert any(f"/jvp({scope})/" in n for n in names), scope
    for scope in backward:
        assert any(f"/transpose(jvp({scope}))/" in n for n in names), scope


def test_gat_step_at_or_1_fits_one_v5e(one_chip, kernel_dispatch):
    """GAT's full-batch step on the sage cell's graph, OR 1.0 (25,000 V,
    1,342,278 E): the one-pass edge softmax keeps nothing per edge between
    the passes, so the step fits one chip's HBM (the three-pass softmax
    needed 37 GB there), its backward is a reverse kernel pass a layer
    with no scatter, and no per-edge array narrower than a feature tile,
    [E, H], is allocated."""
    g, compiled = _fullbatch_step("gat", 1.0, one_chip)
    assert g.num_edges == 1342278
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    print(f"GAT step at OR 1.0: HLO temp "
          f"{mem.temp_size_in_bytes / 2 ** 30:.2f} GiB, used "
          f"{used / 2 ** 30:.2f} GiB")
    assert used < 15.75 * 2 ** 30, used
    hlo = compiled.as_text()
    assert _kernel_calls(hlo) == sorted(
        ["segment_spmm_softmax", "segment_spmm_softmax_bwd"] * 3)
    assert not _softmax_backward_scatters(hlo)
    messages = 2 * g.num_edges
    narrow = {int(e) for e in re.findall(r"f32\[(\d+),4\]", hlo)}
    assert not {e for e in narrow if e >= messages}, narrow


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
