"""The readers on the recorded traces, and the program's own spans and
scopes as the benchmark sees them.

`testdata/tiny_trace` was recorded on a TPU v5e before the program put
its spans on the profiler's clock: the readers that predate it must read
what they read then. `testdata/tiny_trace_spans` is the same window (a
sage step at OR 0.02, widths 128, 3 steps) recorded with the program's
tracer installed, beside the compiled step's instruction -> op_name map
(`op_names.json`, from `repro.analysis.hlo.op_names_from_hlo`).
"""

from __future__ import annotations

import glob
import json
import os
import types

import pytest

from bench import cells, trace

HERE = os.path.dirname(__file__)
RECORDED = os.path.join(HERE, "testdata", "tiny_trace")
SPANS = os.path.join(HERE, "testdata", "tiny_trace_spans")
# the OR 0.02 graph both traces ran on: 500 vertices, 18,824 stored edges
VERTICES, MESSAGES = 500, 2 * 18824


def recorded_run(path):
    """What a reader sees of a traced run of the recorded window."""
    base = cells.resolve("sage.fullbatch.or")
    cell = cells.Cell(base.name, {**base.config, "feature_dim": 128,
                                  "hidden_dim": 128},
                      {**base.traffic, "scale": 0.02}, 1, base.end_to_end,
                      base.per_layer, base.limits)
    summary = trace.reduce(trace.load(path))
    return types.SimpleNamespace(
        cell=cell, peaks=cells.peaks("TPU v5 lite"), trace=summary,
        kernel_calls=3, num_messages=MESSAGES, num_vertices=VERTICES,
        window_s=summary.window_s, units=3.0)


def test_readers_read_the_first_recorded_trace_as_before():
    run = recorded_run(RECORDED)
    read = lambda name: cells.reader(name)(run)  # noqa: E731
    assert read("idle_share.fullbatch") == pytest.approx(82.42214122590195,
                                                         rel=1e-12)
    assert read("segment_reduce_roofline") == pytest.approx(
        111.64321940080329, rel=1e-12)
    # the kernel's calls there carry the name they had before they were
    # named by combiner: the new reader stays silent
    assert read("aggregate.kernel_s") is None
    labels = [(k, round(v * 1e9)) for k, v in run.trace.gaps[:5]]
    assert labels == [("bench/window", 5623656), ("bench/window", 5183886),
                      ("bench/window", 4630742),
                      ("bench/fullbatch.train_step", 1211180),
                      ("bench/fullbatch.train_step", 1228)]


def kernel_run(names, kernel_calls=3, regime="fullbatch"):
    events = [(f"%{n} = f32[512,128]{{1,0}} custom-call(%a, %b), "
               f"custom_call_target=\"tpu_custom_call\"", 0.002 * (i + 1))
              for i, n in enumerate(names)]
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(traffic={"regime": regime}),
        trace=trace.Summary(1.0, 0.5, 1, {}, events, []),
        kernel_calls=kernel_calls)


def test_kernel_seconds_read_whole_steps_of_named_calls():
    read = cells.reader("aggregate.kernel_s")
    sums = [f"segment_spmm_sum.{i}" for i in range(3, 9)]
    # two steps of three calls: 2+4+...+12 ms over 2 epochs
    assert read(kernel_run(sums)) == pytest.approx(0.042 / 2)
    gat = ["segment_spmm_max.3", "segment_spmm_sum.4", "segment_spmm_sum.5"]
    assert read(kernel_run(gat)) == pytest.approx(0.012)
    assert read(kernel_run(sums[:5])) is None          # a step cut in two
    assert read(kernel_run(sums, kernel_calls=None)) is None
    assert read(kernel_run(sums, regime="minibatch")) is None
    old = ["jvp_jit_segment_spmm__.6"] * 3
    assert read(kernel_run(old)) is None
    assert read(kernel_run(sums[:3] + old)) is None


def host_spans(path):
    """(name, start_ns, end_ns) of the `bench/` and `repro/` host events
    of the one trace under `path`."""
    import jax

    (xplane,) = glob.glob(os.path.join(path, "*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(xplane)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in prof.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(("bench/", "repro/"))]


def test_readers_read_the_recorded_trace_with_program_spans():
    """The window recorded with the program's tracer on: the kernel's
    calls carry their combiner's name, the new reader reads them, and the
    readers that predate the spans read it as they read any trace."""
    run = recorded_run(SPANS)
    s = run.trace
    assert s.chips == 1 and 0 < s.busy_s < s.window_s
    assert len(s.kernel_events) == 9
    heads = {trace.short_name(n).split(" = ")[0].rsplit(".", 1)[0]
             for n, _ in s.kernel_events}
    assert heads == {"segment_spmm_sum"}
    assert cells.reader("aggregate.kernel_s")(run) == pytest.approx(
        sum(sec for _, sec in s.kernel_events) / 3)
    assert cells.reader("segment_reduce_roofline")(run) > 0
    assert 0 < cells.reader("idle_share.fullbatch")(run) < 100
    assert all(k.startswith("bench/") for k, _ in s.breakdown()["idle_gaps"])


def test_recorded_program_spans_nest_in_the_steps():
    """Each of the window's steps holds one `repro/fullbatch.step`, and in
    it the dispatch phase and then the wait phase."""
    spans = host_spans(SPANS)
    steps = sorted((s, e) for n, s, e in spans if n == "bench/step")
    assert len(steps) == 3
    for s0, e0 in steps:
        inside = {n: (s, e) for n, s, e in spans
                  if n.startswith("repro/fullbatch.step") and s0 <= s <= e <= e0}
        assert set(inside) == {"repro/fullbatch.step",
                               "repro/fullbatch.step.dispatch",
                               "repro/fullbatch.step.wait"}
        step = inside["repro/fullbatch.step"]
        d, w = (inside["repro/fullbatch.step.dispatch"],
                inside["repro/fullbatch.step.wait"])
        assert step[0] <= d[0] <= d[1] <= w[0] <= w[1] <= step[1]


def test_recorded_op_map_names_the_edge_path():
    """The compiled step's op_names (as the chip compiled it) resolve
    nearly all of the window's device time, and group it into the edge
    path's gathers, the kernel and the backward, which together take
    less than the steps' busy time."""
    with open(os.path.join(SPANS, "op_names.json")) as f:
        op_map = json.load(f)
    ev = trace.load(SPANS)
    run = recorded_run(SPANS)
    seconds = {}
    w0, w1 = [(s, s + d) for _, n, s, d in ev.host_spans
              if n == trace.WINDOW][0]
    for _, name, s, d in ev.device_ops:
        lo, hi = max(s, w0), min(s + d, w1)
        if hi > lo:
            head = trace.short_name(name).split(" = ")[0]
            seconds[head] = seconds.get(head, 0.0) + (hi - lo) * 1e-9
    total = sum(seconds.values())
    resolved = sum(v for k, v in seconds.items() if k in op_map)
    assert resolved >= 0.9 * total
    groups = {"gather": 0.0, "kernel": 0.0, "backward": 0.0}
    for head, sec in seconds.items():
        name = op_map.get(head, "")
        if "segment_spmm_" in name:
            groups["kernel"] += sec
        elif "transpose(" in name and "edge." in name:
            groups["backward"] += sec
        elif "edge.gather" in name or "edge.reorder" in name:
            groups["gather"] += sec
    assert all(v > 0 for v in groups.values()), groups
    assert sum(groups.values()) < run.trace.busy_s
    kernel = sum(sec for _, sec in run.trace.kernel_events)
    assert groups["kernel"] == pytest.approx(kernel)
