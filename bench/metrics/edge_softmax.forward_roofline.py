"""edge_softmax.forward_roofline: the edge-softmax forward kernel's share
of its roofline in full-batch GAT cells (%): the least time the chip could
take for the traced window's calls named `segment_spmm_softmax`, over
their device time. The calls are taken as `edge_softmax.forward_s` takes
them, whatever else the step's kernels are; the least time of a call is
counted as `edge_softmax_roofline` counts it, from the graph and the
configuration's widths (the softmax's own work: sums the kernel keeps
besides for a backward are not counted, so they read as a lower share).
"""

import importlib.util
from pathlib import Path


def _load(metric: str):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"),
        Path(__file__).with_name(metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_forward = _load("edge_softmax.forward_s")
_roofline = _load("edge_softmax_roofline")


def read(run):
    got = _forward.step_calls(run, _forward.NAME)
    if got is None or not run.peaks:
        return None
    named, steps = got
    least = 0.0
    for heads, width in _roofline.widths(run.cell.config):
        flops, nbytes = _roofline.call_work(run.num_messages,
                                            run.num_vertices, heads, width)
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * steps / sum(named)
