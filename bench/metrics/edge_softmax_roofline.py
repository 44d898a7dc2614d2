"""edge_softmax_roofline: the Pallas edge-softmax kernel's share of its
roofline in full-batch GAT cells (%): the least time the chip could take
for the traced window's calls named `segment_spmm_softmax`, over their
device time.

Each call is one layer's edge softmax; its work is counted from the graph,
whatever implements it: with M the real messages (both directions of every
stored edge, no padding), V the rows, H the heads and w = H x head width,

    flops = 2 * M * w + M * H
    bytes = 4 * (M * w + M + V * (w + 4 * H))

(each message's weighted row into the numerator and its weight into the
denominator; the source rows read, one index a message, and per row the
numerator and 4 H values of its score and softmax tables). Its least time
is max(flops / peak flops, bytes / peak bandwidth); on a v5e the bytes
decide. The widths come from the configuration. The events must be whole steps of one call a
layer, and the compiled step must hold as many kernel calls: otherwise the
metric is left out.
"""

from bench import trace

NAME = "segment_spmm_softmax"


def widths(config: dict) -> list:
    """(heads, w) of each layer's edge softmax, in issue order."""
    heads = config["gat_heads"]
    outs = ([config["hidden_dim"]] * (config["num_layers"] - 1)
            + [config["num_classes"]])
    return [(heads, heads * max(d // heads, 1)) for d in outs]


def call_work(messages: int, rows: int, heads: int, width: int) -> tuple:
    """(flops, bytes) one layer's edge softmax needs."""
    return (2 * messages * width + messages * heads,
            4 * (messages * width + messages + rows * (width + 4 * heads)))


def softmax_events(run) -> list:
    """Seconds of each traced softmax call, or None unless the kernel
    events are all softmax calls making whole steps of the compiled
    step's kernel calls, one a layer."""
    if (run.trace is None or run.cell.traffic["regime"] != "fullbatch"
            or run.cell.config.get("model") != "gat" or not run.kernel_calls
            or run.kernel_calls != run.cell.config["num_layers"]):
        return None
    events = run.trace.kernel_events
    named = [seconds for name, seconds in events
             if trace.short_name(name).split(" = ")[0].rsplit(".", 1)[0]
             == NAME]
    if not named or len(named) != len(events) or len(named) % run.kernel_calls:
        return None
    return named


def read(run):
    named = softmax_events(run)
    if named is None or not run.peaks:
        return None
    steps = len(named) // run.kernel_calls
    least = 0.0
    for heads, width in widths(run.cell.config):
        flops, nbytes = call_work(run.num_messages, run.num_vertices, heads,
                                  width)
        least += max(flops / run.peaks["bf16_flops_per_s"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * steps / sum(named)
