"""The flash-attention kernels against their roofline where the query
and key heads are wider than the value heads (latent attention's
non-absorbed form): the least time the chip could take for every
layer's score and value products over the causal pairs, forward and
backward, nothing recomputed counted, q, k, v, output and their
cotangents moved once (``flops_mla_moe_train.flash_work``) — over the
device time of the kernels' events in one step.  The kernels' events
are the step's Mosaic custom calls that carry no name of their own
(``...[tpu_custom_call]``); the grouped matmuls of the same step are
named (``grouped_matmul*``) and left out."""

from benchmarks import flops_mla_moe_train as fl
from benchmarks import trace_reduce
from benchmarks.runners.common import load_module

#: a Mosaic call whose name is not a grouped matmul's
KERNEL = r"^(?!grouped_matmul).*\[tpu_custom_call\]$"


def reduce(trace, counters, cell):
    seconds, events = trace_reduce.op_seconds(trace, KERNEL)
    steps = len(load_module("step_ms.train").step_events(trace))
    if cell.get("peaks") is None or not events or not steps:
        return None
    model = cell["config"]
    ops, nbytes = fl.flash_work(
        model, counters["rows_per_step"], counters["seq_len"],
        model["dtype"])
    least_s, _ = fl.roofline_seconds(
        ops, nbytes, cell["peaks"], model["dtype"])
    return 100.0 * least_s / (seconds / steps)
