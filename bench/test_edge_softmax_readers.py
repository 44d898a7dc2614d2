"""The readers of the edge-softmax kernel's calls by name
(`edge_softmax.forward_s`, `edge_softmax.forward_roofline`,
`edge_softmax.backward_s`) on hand-made kernel events: the calls of whole
steps, with or without the reverse pass, and silence wherever the window
holds anything else."""

from __future__ import annotations

import types

import pytest

from bench import cells, trace


def softmax_run(names, kernel_calls, model="gat", regime="fullbatch"):
    events = [(f"%{n} = f32[25088,512]{{1,0}} custom-call(%a, %b), "
               f"custom_call_target=\"tpu_custom_call\"", seconds)
              for n, seconds in names]
    config = cells.resolve("gat4.fullbatch.or").config
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(
            config={**config, "model": model, "num_layers": 3},
            traffic={"regime": regime}),
        trace=trace.Summary(1.0, 0.5, 1, {}, events, []),
        kernel_calls=kernel_calls, num_messages=2684556, num_vertices=25000,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def step(i):
    """One GAT step: three forward calls, then the backward's three."""
    fwd = [(f"segment_spmm_softmax.{6 * i + j}", s)
           for j, s in enumerate((0.02, 0.02, 0.001))]
    bwd = [(f"segment_spmm_softmax_bwd.{6 * i + 3 + j}", s)
           for j, s in enumerate((0.01, 0.05, 0.06))]
    return fwd + bwd


def test_backward_seconds_read_whole_steps_of_reverse_passes():
    read = cells.reader("edge_softmax.backward_s")
    # two steps: 10 + 50 + 60 ms of reverse passes each
    assert read(softmax_run(step(0) + step(1), 6)) == pytest.approx(0.12)
    assert read(softmax_run(step(0), 6)) == pytest.approx(0.12)
    # silent on a step cut in two, on a step whose compiled program holds
    # other calls, on another model or regime, and without a trace
    assert read(softmax_run(step(0) + step(1)[:4], 6)) is None
    assert read(softmax_run(step(0) + step(1), 5)) is None
    assert read(softmax_run(step(0) + step(1), 6, model="sage")) is None
    assert read(softmax_run(step(0) + step(1), 6,
                            regime="minibatch")) is None
    assert read(softmax_run(step(0), None)) is None
    run = softmax_run(step(0), 6)
    run.trace = None
    assert read(run) is None


def test_backward_seconds_silent_on_a_program_without_them():
    """A step of forward calls alone, as a program whose backward is no
    kernel compiles it, reads nothing and does not raise."""
    read = cells.reader("edge_softmax.backward_s")
    forward = [(f"segment_spmm_softmax.{j}", 0.02) for j in range(6)]
    assert read(softmax_run(forward, 3)) is None
    assert read(softmax_run([], 3)) is None


def forward_only(i):
    """One step of a program whose backward is no kernel: three calls."""
    return [(f"segment_spmm_softmax.{3 * i + j}", s)
            for j, s in enumerate((0.02, 0.02, 0.001))]


# the three calls' least time (edge_softmax_roofline's count): 6.79 ms for
# each 512 wide layer and 0.23 ms for the 16 wide last one, bytes bound
LEAST = (2 * 4 * (2684556 * 512 + 2684556 + 25000 * (512 + 16))
         + 4 * (2684556 * 16 + 2684556 + 25000 * (16 + 16))) / 819e9


@pytest.mark.parametrize("steps,calls", [(step, 6), (forward_only, 3)])
def test_forward_readers_read_whole_steps(steps, calls):
    """With the reverse pass or without, two steps of 20 + 20 + 1 ms of
    forward calls read 41 ms an epoch and the roofline share of that."""
    seconds = cells.reader("edge_softmax.forward_s")
    roofline = cells.reader("edge_softmax.forward_roofline")
    assert LEAST == pytest.approx(0.0138, abs=1e-4)
    run = softmax_run(steps(0) + steps(1), calls)
    assert seconds(run) == pytest.approx(0.041)
    assert roofline(run) == pytest.approx(100 * LEAST / 0.041)
    assert seconds(softmax_run(steps(0), calls)) == pytest.approx(0.041)
    # silent on a step cut in two, a foreign kernel in place of a call,
    # another model or regime, no trace, no peaks
    cut = softmax_run(steps(0) + steps(1)[:-1], calls)
    foreign = softmax_run([("segment_spmm_sum.9", 0.01)] + steps(0)[1:],
                          calls)
    for bad in (cut, foreign, softmax_run(steps(0), calls, model="sage"),
                softmax_run(steps(0), calls, regime="minibatch"),
                softmax_run(steps(0), None)):
        assert seconds(bad) is None and roofline(bad) is None
    run.trace = None
    assert seconds(run) is None and roofline(run) is None
    run = softmax_run(steps(0), calls)
    run.peaks = {}
    assert roofline(run) is None


def test_forward_roofline_matches_the_whole_step_reader_without_a_backward():
    """Where every kernel call is a forward call, the forward roofline
    reads what `edge_softmax_roofline` reads; with the reverse pass in the
    step, that one is silent and the forward one still reads."""
    whole = cells.reader("edge_softmax_roofline")
    forward = cells.reader("edge_softmax.forward_roofline")
    run = softmax_run(forward_only(0) + forward_only(1), 3)
    assert forward(run) == pytest.approx(whole(run))
    run = softmax_run(step(0) + step(1), 6)
    assert whole(run) is None
    assert forward(run) == pytest.approx(100 * LEAST / 0.041)
