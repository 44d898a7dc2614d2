"""edge_softmax.forward_s: device seconds an epoch of the edge-softmax
forward kernel in full-batch GAT cells: the traced window's Pallas calls
named `segment_spmm_softmax` (its backward's `segment_spmm_softmax_bwd`
calls are not counted), over the steps they make up (one step is one
epoch). The window's kernel events must be whole steps of the compiled
step's kernel calls, and each step must hold one such call a layer;
otherwise the metric is left out. It reads whatever else the step's
kernels are, so it reads a program whose backward is a kernel too.
"""

from bench import trace

NAME = "segment_spmm_softmax"


def step_calls(run, name: str):
    """(seconds of each traced call named `name`, steps in the window), or
    None unless the kernel events are whole steps with one such call a
    layer."""
    if (run.trace is None or run.cell.traffic["regime"] != "fullbatch"
            or run.cell.config.get("model") != "gat" or not run.kernel_calls):
        return None
    events = run.trace.kernel_events
    if not events or len(events) % run.kernel_calls:
        return None
    named = [seconds for call, seconds in events
             if trace.short_name(call).split(" = ")[0].rsplit(".", 1)[0]
             == name]
    steps = len(events) // run.kernel_calls
    if not named or len(named) != steps * run.cell.config["num_layers"]:
        return None
    return named, steps


def read(run):
    got = step_calls(run, NAME)
    if got is None:
        return None
    named, steps = got
    return sum(named) / steps
