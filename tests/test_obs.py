"""The observability layer's acceptance gate (repro.obs + gnn_trace).

  * the disabled tracer is a true no-op: zero events recorded, and a
    traced run's loss trajectory is bitwise identical to an untraced one
  * the phase spans ARE the StepMetrics phase times (one timing source),
    so the pinned phases-sum-to-wall contract survives the migration
  * the Chrome trace-event export round-trips through its own loader:
    every B has its E, per-track timestamps are monotonic, counters and
    the two clock domains (wall / simulated serving clock) land on
    separate pids, and the schema tag is present
  * reconciliation is exact for fp32: measured fetch wire bytes equal the
    codec formula, traced full-batch collectives equal collective_budget /
    sync_wire_bytes_per_round, and a single injected byte flips the
    report to exit code 1 (the seeded red path)
  * `study.serve_result_row` carries the queue-wait / service-time
    breakdown columns that attribute p99 to queueing vs compute
"""

import json
import threading

import numpy as np
import pytest

from repro.core.edge_partition import partition_edges
from repro.core.graph import generate_graph
from repro.core.vertex_partition import partition_vertices
from repro.gnn.fullbatch import FullBatchTrainer
from repro.gnn.minibatch import MiniBatchTrainer
from repro.gnn.models import GNNSpec
from repro.obs import (
    TRACE_SCHEMA,
    Tracer,
    get_tracer,
    install,
    load_trace,
    phase_means,
    reconcile,
    to_chrome_trace,
    tracing,
    uninstall,
    validate_chrome_trace,
    write_trace,
)


@pytest.fixture(scope="module")
def tiny_graph():
    return generate_graph("social", 150, 900, seed=3)


@pytest.fixture(scope="module")
def node_setup(tiny_graph):
    g = tiny_graph
    rng = np.random.default_rng(0)
    feats = rng.normal(size=(g.num_vertices, 12)).astype(np.float32)
    labels = rng.integers(0, 5, g.num_vertices).astype(np.int32)
    train = rng.random(g.num_vertices) < 0.4
    return g, feats, labels, train


def _minibatch(node_setup, *, codec=None, overlap=False, steps=3):
    g, feats, labels, train = node_setup
    owner = partition_vertices(g, 2, "metis", seed=0)
    spec = GNNSpec(model="sage", feature_dim=12, hidden_dim=8, num_classes=5,
                   num_layers=2)
    tr = MiniBatchTrainer.build(g, owner, 2, spec, feats, labels, train,
                                global_batch=32, seed=3, codec=codec,
                                overlap=overlap)
    ms = [tr.train_step() for _ in range(steps)]
    tr.close()
    return tr, ms


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_disabled_tracer_records_nothing():
    """The module singleton starts disabled and stays empty no matter how
    much the instrumentation fires."""
    tr = get_tracer()
    assert not tr.enabled
    before = len(tr)
    with tr.span("x", cat="test"):
        pass
    tr.add("c", 123)
    tr.gauge("g", 1.0)
    tr.collective("all-reduce", 64)
    assert len(tr) == before == 0
    assert tr.total("c") is None


def test_tracing_no_behavior_change(node_setup):
    """Bitwise-identical loss trajectory with and without the tracer —
    the instrumentation may observe, never perturb."""
    _, ms_off = _minibatch(node_setup)
    with tracing() as tr:
        _, ms_on = _minibatch(node_setup)
        assert len(tr) > 0
    assert [m.loss for m in ms_off] == [m.loss for m in ms_on]


def test_span_records_thread_and_duration():
    with tracing() as tr:
        def work():
            with tr.span("worker.op", cat="test", track="pool"):
                pass
        t = threading.Thread(target=work, name="pool-0")
        t.start()
        t.join()
        with tr.span("main.op", cat="test"):
            pass
    spans = tr.spans()
    assert {s.name for s in spans} == {"worker.op", "main.op"}
    by_name = {s.name: s for s in spans}
    assert by_name["worker.op"].thread == "pool-0"
    assert all(s.t1 >= s.t0 for s in spans)


def test_counter_totals_survive_ring_wrap():
    """`total()` is exact even after the event ring truncates."""
    with tracing(capacity=8) as tr:
        for _ in range(100):
            tr.add("bytes", 3)
    assert tr.total("bytes") == 300
    assert len(tr.counters("bytes")) == 8  # ring kept only the tail


def test_phase_clock_sums_to_wall():
    with tracing() as tr:
        clock = tr.phase_clock(cat="test")
        parts = [clock.split(f"p{i}") for i in range(4)]
    spans = tr.spans()
    assert len(spans) == 4
    # contiguous: each phase starts exactly where the previous ended
    for a, b in zip(spans, spans[1:]):
        assert a.t1 == b.t0
    assert sum(parts) == spans[-1].t1 - spans[0].t0


# ---------------------------------------------------------------------------
# phase accounting migration (satellite 1: one timing source)
# ---------------------------------------------------------------------------


def test_step_metrics_phases_are_the_spans(node_setup):
    """The serial engine's StepMetrics phase times and the recorded spans
    are the same numbers — not two parallel clocks."""
    with tracing() as tr:
        _, ms = _minibatch(node_setup, steps=2)
    by_name = {}
    for s in tr.spans():
        by_name.setdefault(s.name, []).append(s)
    for phase in ("sample", "fetch", "transfer"):
        spans = by_name[f"pipeline.{phase}"]
        assert len(spans) == len(ms)
        for s, m in zip(spans, ms):
            assert s.duration == getattr(m, f"{phase}_time_host")
    # serial contract stays pinned: phases sum exactly to the step wall
    for m in ms:
        assert (m.sample_time_host + m.fetch_time_host
                + m.transfer_time_host + m.compute_time_host
                ) == pytest.approx(m.step_wall_host, abs=0, rel=0)


def test_phase_means_matches_study(node_setup):
    from repro.core import study

    _, ms = _minibatch(node_setup, steps=3)
    assert study.host_phase_means(ms) == phase_means(ms)
    pm = phase_means(ms)
    assert set(pm) == {"host_sample_time", "host_fetch_time",
                       "host_transfer_time", "host_compute_time",
                       "host_step_wall", "overlap_efficiency"}


# ---------------------------------------------------------------------------
# export round-trip (satellite 4)
# ---------------------------------------------------------------------------


def test_export_round_trip(tmp_path, node_setup):
    with tracing() as tr:
        _minibatch(node_setup, steps=2)
    path = tmp_path / "trace.json"
    payload = write_trace(str(path), tr)
    assert validate_chrome_trace(payload) == []
    loaded = load_trace(str(path))
    assert loaded["otherData"]["schema"] == TRACE_SCHEMA
    events = loaded["traceEvents"]
    # every B paired with an E, per (pid, tid)
    open_stacks = {}
    for e in events:
        key = (e["pid"], e["tid"])
        if e["ph"] == "B":
            open_stacks.setdefault(key, []).append(e["name"])
        elif e["ph"] == "E":
            assert e["name"] in open_stacks.get(key, [])
            open_stacks[key].remove(e["name"])
    assert all(not v for v in open_stacks.values())
    # per-track timestamps monotonic non-decreasing
    last = {}
    for e in events:
        if e["ph"] == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last.get(key, 0.0)
        last[key] = e["ts"]


def test_export_merges_tracers_and_clocks():
    t1 = Tracer()
    t1.record_span("a", 1.0, 2.0, cat="x")
    t2 = Tracer()
    t2.record_span("b", 5.0, 6.0, cat="x", clock="model", track="sim")
    t2.add("wire", 7, t=5.5, track="wire")
    payload = to_chrome_trace([t1, t2])
    assert validate_chrome_trace(payload) == []
    by_ph = {}
    for e in payload["traceEvents"]:
        by_ph.setdefault(e["ph"], []).append(e)
    pids = {e["pid"] for e in by_ph["B"]}
    assert len(pids) == 2  # wall clock and model clock are separate pids
    assert by_ph["C"][0]["name"] == "wire"
    assert by_ph["C"][0]["args"] == {"value": 7.0}


def test_validator_flags_unpaired_and_nonmonotonic():
    bad = {"otherData": {"schema": TRACE_SCHEMA}, "traceEvents": [
        {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 2.0,
         "cat": "x", "args": {}},
        {"ph": "E", "name": "zzz", "pid": 1, "tid": 1, "ts": 1.0},
    ]}
    problems = validate_chrome_trace(bad)
    assert any("no open B" in p for p in problems)
    assert any("unclosed" in p for p in problems)
    assert any(" < " in p for p in problems)


# ---------------------------------------------------------------------------
# reconciliation (satellite 4: green fp32, seeded red path)
# ---------------------------------------------------------------------------


def test_reconcile_minibatch_fp32_exact(node_setup):
    with tracing() as tr:
        trainer, ms = _minibatch(node_setup, steps=3)
        checks = reconcile.reconcile_minibatch(trainer, ms, tracer=tr)
    by_q = {c.quantity: c for c in checks}
    assert by_q["fetch.wire_bytes"].level == "ok"
    assert by_q["fetch.wire_bytes"].tol_rel == 0.0  # bitwise contract
    assert by_q["fetch.miss_bytes"].level == "ok"
    assert by_q["phase.closure"].level == "ok"
    assert all(c.level != "error" for c in checks)


def test_reconcile_minibatch_int8_ratio(node_setup):
    with tracing() as tr:
        trainer, ms = _minibatch(node_setup, codec="int8", steps=3)
        checks = reconcile.reconcile_minibatch(trainer, ms, tracer=tr)
    by_q = {c.quantity: c for c in checks}
    assert by_q["fetch.wire_bytes"].level == "ok"  # still exact: formula
    assert by_q["fetch.wire_ratio"].level == "ok"  # ~0.25 + meta slack
    assert abs(by_q["fetch.wire_ratio"].measured - 0.25) < 0.05


def test_reconcile_minibatch_overlap_skips_fetch(node_setup):
    """The prefetcher fetches ahead of consumption, so the pipelined
    engine's fetch/phase checks must warn-skip, never error."""
    with tracing() as tr:
        trainer, ms = _minibatch(node_setup, overlap=True, steps=2)
        checks = reconcile.reconcile_minibatch(trainer, ms, tracer=tr)
    by_q = {c.quantity: c for c in checks}
    assert by_q["fetch.wire_bytes"].level == "warn"
    assert by_q["phase.closure"].level == "warn"
    assert reconcile.build_report(checks).exit_code == 0


def test_reconcile_injected_byte_is_an_error(node_setup):
    """The seeded red path: one stray byte through the real measured
    counter must flip the exact check and the report's exit code."""
    with tracing() as tr:
        trainer, ms = _minibatch(node_setup, steps=2)
        tr.add("fetch.wire_bytes", 1)
        checks = reconcile.reconcile_minibatch(trainer, ms, tracer=tr)
    by_q = {c.quantity: c for c in checks}
    assert by_q["fetch.wire_bytes"].level == "error"
    report = reconcile.build_report(checks)
    assert report.exit_code == 1
    assert report.counts["error"] == 1


@pytest.mark.parametrize("model", ["sage", "gat"])
def test_reconcile_fullbatch_halo_exact(node_setup, model):
    g, feats, labels, train = node_setup
    spec = GNNSpec(model=model, feature_dim=12, hidden_dim=8, num_classes=5,
                   num_layers=2)
    a = partition_edges(g, 4, "hep100", seed=0)
    with tracing() as tr:
        trainer = FullBatchTrainer.build(g, a, 4, spec, feats, labels,
                                         train, sync_mode="halo", mode="sim")
        trainer.train_step()
        checks = reconcile.reconcile_fullbatch(trainer, tracer=tr)
    by_q = {c.quantity: c for c in checks}
    assert by_q["sync.count.all-to-all"].level == "ok"
    assert by_q["sync.cluster_bytes.all-to-all"].level == "ok"
    assert by_q["sync.wire_bytes.forward"].level == "ok"
    assert by_q["epoch.wire_bytes"].level == "ok"
    # every full-batch byte check is bitwise for fp32
    assert all(c.tol_rel == 0.0 for c in checks)


def test_reconcile_fullbatch_requires_trace_before_compile(node_setup):
    """Installing the tracer after the step compiled yields a warn-level
    skip, never a silent pass."""
    g, feats, labels, train = node_setup
    spec = GNNSpec(model="sage", feature_dim=12, hidden_dim=8,
                   num_classes=5, num_layers=2)
    a = partition_edges(g, 2, "hep100", seed=0)
    trainer = FullBatchTrainer.build(g, a, 2, spec, feats, labels, train,
                                     sync_mode="halo", mode="sim")
    trainer.train_step()  # compiles untraced
    with tracing() as tr:
        trainer.train_step()  # cached executable: no trace, no events
        checks = reconcile.reconcile_fullbatch(trainer, tracer=tr)
    assert len(checks) == 1
    assert checks[0].level == "warn"


# ---------------------------------------------------------------------------
# serving breakdown columns (satellite 3) + serve reconciliation
# ---------------------------------------------------------------------------


def _serving_run(node_setup, requests=80):
    from repro.core.partition_book import build_vertex_book
    from repro.gnn.inference import LayerwiseInference
    from repro.gnn.models import init_params
    from repro.serve import build_serving, run_serving_sim

    g, feats, _, _ = node_setup
    spec = GNNSpec(model="sage", feature_dim=12, hidden_dim=8,
                   num_classes=5, num_layers=2)
    params = init_params(spec, seed=0)
    a = partition_edges(g, 2, "hep100", seed=0)
    eng = LayerwiseInference.build(g, a, 2, spec, params, feats)
    embeddings = eng.run()
    owner = eng.book.master_assignment()
    vbook = build_vertex_book(g, owner, 2)
    engines, batchers, store = build_serving(
        g, vbook, spec, params, embeddings, hops=1, fanout=6, max_batch=8,
        max_wait=5e-4, seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, g.num_vertices, requests)
    arrivals = np.sort(rng.uniform(0.0, requests / 300.0, requests))
    report = run_serving_sim(engines, batchers, owner, ids, arrivals)
    return spec, report, store


def test_serve_result_row_breakdown_columns(node_setup):
    from repro.core import study

    spec, report, store = _serving_run(node_setup)
    row = study.serve_result_row(
        "OR", "hep100", 2, spec, report, qps=300.0, hops=1, fanout=6,
        max_batch=8, max_wait=5e-4, cache_policy="none", cache_budget=0,
        partition_time=0.0, partition_quality=1.0)
    for col in ("queue_wait_p50", "queue_wait_p99", "queue_wait_mean",
                "service_p50", "service_p99", "service_mean_req",
                "p99_queue_share"):
        assert col in row, col
    # queue wait + service == latency, so the breakdown means must close
    assert (row["queue_wait_mean"] + row["service_mean_req"]
            == pytest.approx(row["latency_mean"], rel=1e-9))
    assert 0.0 <= row["p99_queue_share"] <= 1.0


def test_reconcile_serving_exact(node_setup):
    with tracing() as tr:
        _, report, store = _serving_run(node_setup)
        checks = reconcile.reconcile_serving(report, store, tracer=tr)
    by_q = {c.quantity: c for c in checks}
    assert by_q["serve.fetch.wire_bytes"].level == "ok"
    assert by_q["serve.fetch.stats_wire_bytes"].level == "ok"
    assert by_q["serve.latency.closure"].level == "ok"
    # the model-clock spans carry the request lifecycle
    tracks = {s.track for s in tr.spans() if s.clock == "model"}
    assert any(t and t.endswith(".queue") for t in tracks)


# ---------------------------------------------------------------------------
# the CLI gate end to end (satellite 4 + acceptance)
# ---------------------------------------------------------------------------


def test_gnn_trace_cli_green_and_red(tmp_path):
    from repro.launch import gnn_trace

    out_trace = tmp_path / "t.json"
    out_json = tmp_path / "r.json"
    argv = ["--scale", "0.01", "--k", "2", "--steps", "1",
            "--requests", "30", "--out-trace", str(out_trace),
            "--out-json", str(out_json)]
    assert gnn_trace.main(argv) == 0
    report = json.loads(out_json.read_text())
    assert report["schema"] == "gnn-trace-report/v2"
    assert report["counts"]["error"] == 0
    assert set(report["programs"]) == {"fullbatch-halo", "fullbatch-ring",
                                       "minibatch", "serve"}
    loaded = load_trace(str(out_trace))
    assert loaded["otherData"]["schema"] == TRACE_SCHEMA

    assert gnn_trace.main(argv + ["--inject-violation"]) == 1
    report = json.loads(out_json.read_text())
    assert report["exit_code"] == 1
    bad = [c for c in report["checks"] if c["level"] == "error"]
    assert len(bad) == 1
    assert bad[0]["quantity"] == "fetch.wire_bytes"


def test_install_uninstall_restores_null():
    prev = get_tracer()
    t = install(Tracer())
    assert get_tracer() is t
    uninstall()
    assert get_tracer() is prev
    assert not get_tracer().enabled


# ---------------------------------------------------------------------------
# the profiler's clock: live spans as `repro/` annotations, JAX's compiles,
# garbage collections
# ---------------------------------------------------------------------------


def _fullbatch_trainer(node_setup):
    g, feats, labels, train = node_setup
    spec = GNNSpec(model="sage", feature_dim=12, hidden_dim=8, num_classes=5,
                   num_layers=2, agg_backend="tiled")
    a = partition_edges(g, 1, "random", seed=0)
    trainer = FullBatchTrainer.build(g, a, 1, spec, feats, labels, train,
                                     sync_mode="halo", mode="sim")
    trainer.train_step()  # compiles outside the measured part
    return trainer


def _profile_host_events(log_dir, fn) -> list:
    """(line, name, start_ns, end_ns) of every host event of a JAX profile
    of `fn()`, which runs inside a `caller/step` annotation."""
    import glob
    import os

    import jax

    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation("caller/step"):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    prof = jax.profiler.ProfileData.from_file(path)
    return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in prof.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_step_phases_land_on_the_profile(tmp_path, node_setup):
    """With an enabled tracer the full-batch step's span and its two
    contiguous phases are annotations of the profile, nested in the
    caller's; with the tracer disabled the profile holds no `repro/`
    event."""
    trainer = _fullbatch_trainer(node_setup)
    with tracing() as tr:
        host = _profile_host_events(tmp_path / "on", trainer.train_step)
    by_name = {n: (ln, s, e) for ln, n, s, e in host}
    line, s0, e0 = by_name["caller/step"]
    names = ("repro/fullbatch.step", "repro/fullbatch.step.dispatch",
             "repro/fullbatch.step.wait")
    for name in names:
        ln, s, e = by_name[name]
        assert ln == line and s0 <= s <= e <= e0, name
    dispatch, wait = by_name[names[1]], by_name[names[2]]
    step = by_name[names[0]]
    assert step[1] <= dispatch[1] <= dispatch[2] <= wait[1] <= wait[2] <= step[2]
    recorded = {s.name for s in tr.spans()}
    assert {n[len("repro/"):] for n in names} <= recorded
    host = _profile_host_events(tmp_path / "off", trainer.train_step)
    assert not [n for _, n, _, _ in host if n.startswith("repro/")]


def _count_annotations(monkeypatch) -> list:
    """Make every `jax.profiler.TraceAnnotation` record its name when it
    is built, and whether it was closed."""
    import jax

    made = []

    class Counted(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            super().__init__(name, **kw)
            made.append([name, False])
            self._i = len(made) - 1

        def __exit__(self, *exc):
            made[self._i][1] = True
            return super().__exit__(*exc)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counted)
    return made


def test_disabled_tracer_builds_no_annotation(monkeypatch, node_setup):
    trainer = _fullbatch_trainer(node_setup)
    made = _count_annotations(monkeypatch)
    trainer.train_step()
    with get_tracer().span("x"):
        pass
    get_tracer().phase_clock(first="p").split("p")
    assert made == []
    with tracing():
        trainer.train_step()
    names = [n for n, _ in made]
    assert {"repro/fullbatch.step", "repro/fullbatch.step.dispatch",
            "repro/fullbatch.step.wait"} <= set(names)
    assert all(closed for _, closed in made)


def test_phase_clock_keeps_one_annotation_open(monkeypatch):
    """Each phase is one annotation, named by `first` or the split's
    `then`, closed at the split that closes the phase."""
    made = _count_annotations(monkeypatch)
    with tracing() as tr:
        clock = tr.phase_clock(cat="test", first="p0")
        assert made == [["repro/p0", False]]
        clock.split("p0", then="p1")
        assert made == [["repro/p0", True], ["repro/p1", False]]
        clock.split("p1")
    assert made == [["repro/p0", True], ["repro/p1", True]]
    assert [s.name for s in tr.spans()] == ["p0", "p1"]


def test_jit_counter_counts_nested_compiles_once():
    """JAX reports a jit traced inside another inside the outer one's
    interval: the total is the union's length. Backend compiles are
    counted, cache loads summed apart; nothing is counted while no enabled
    tracer is installed."""
    import jax

    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    backend_ev = "/jax/core/compile/backend_compile_duration"
    span = jax.monitoring.record_event_time_span
    with tracing() as tr:
        span(trace_ev, 10.0, 10.5)    # an inner jit's trace ...
        span(trace_ev, 9.0, 12.0)     # ... inside the outer one's
        span(backend_ev, 12.0, 13.0)
        span(backend_ev, 20.0, 21.0)
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    assert tr.total("jit.compile_s") == pytest.approx(5.0)
    assert tr.total("jit.compiles") == 2
    assert tr.total("jit.cache_load_s") == 0.25
    span(backend_ev, 30.0, 31.0)
    assert tr.total("jit.compiles") == 2


def test_jit_counter_sees_a_real_compile():
    import time

    import jax
    import jax.numpy as jnp

    x = jnp.ones(3)
    inner = jax.jit(lambda v: v * 3.0)
    outer = jax.jit(lambda v: inner(v) + inner(v + 1.0))
    with tracing() as tr:
        t0 = time.perf_counter()
        outer(x).block_until_ready()
        wall = time.perf_counter() - t0
    assert tr.total("jit.compiles") == 1
    assert 0.0 < tr.total("jit.compile_s") <= wall


def test_gc_collections_are_spans_while_installed():
    import gc

    with tracing() as tr:
        gc.collect()
    spans = tr.spans("host.gc")
    assert spans and spans[-1].args == {"generation": 2}
    assert all(s.t1 >= s.t0 for s in spans)
    gc.collect()
    assert len(tr.spans("host.gc")) == len(spans)
