"""edge_softmax.backward_s: device seconds an epoch of the edge softmax's
reverse-pass backward in full-batch GAT cells: the traced window's Pallas
calls named `segment_spmm_softmax_bwd`, over the steps they make up (one
step is one epoch). The events must be as `edge_softmax.forward_s` asks,
one such call a layer; otherwise the metric is left out (a program without
the reverse pass has none).
"""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_edge_softmax_forward_s",
    Path(__file__).with_name("edge_softmax.forward_s.py"))
_forward = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_forward)

NAME = "segment_spmm_softmax_bwd"


def read(run):
    got = _forward.step_calls(run, NAME)
    if got is None:
        return None
    named, steps = got
    return sum(named) / steps
