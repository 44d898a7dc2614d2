"""aggregate.kernel_s: device seconds an epoch of the segment-reduce
kernel in full-batch cells: the traced window's Pallas calls the program
names `segment_spmm_sum` and `segment_spmm_max`, over the steps they make
up (one step is one epoch). Every kernel event must carry one of these
names, and the events must be whole steps of as many calls as the
compiled step holds; otherwise the metric is left out.
"""

from bench import trace

NAMES = ("segment_spmm_sum", "segment_spmm_max")


def read(run):
    if (run.trace is None or run.cell.traffic["regime"] != "fullbatch"
            or not run.kernel_calls):
        return None
    events = run.trace.kernel_events
    named = [seconds for name, seconds in events
             if trace.short_name(name).split(" = ")[0].rsplit(".", 1)[0]
             in NAMES]
    if not named or len(named) != len(events) or len(named) % run.kernel_calls:
        return None
    return sum(named) / (len(named) // run.kernel_calls)
