"""edge_softmax.kernel_s: device seconds an epoch of the edge-softmax
kernel in full-batch GAT cells: the traced window's Pallas calls named
`segment_spmm_softmax`, over the steps they make up (one step is one
epoch). The events must be as `edge_softmax_roofline` asks; otherwise the
metric is left out.
"""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_edge_softmax_roofline",
    Path(__file__).with_name("edge_softmax_roofline.py"))
_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_roofline)


def read(run):
    named = _roofline.softmax_events(run)
    if named is None:
        return None
    return sum(named) / (len(named) // run.kernel_calls)
