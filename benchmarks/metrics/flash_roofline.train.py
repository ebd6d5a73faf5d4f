"""The flash-attention kernel against its roofline: the least time the
chip could take for the kernel's work on this chip's share of a step —
score and value products inside the causal window, forward and
backward, nothing recomputed counted; q, k, v, output and their
cotangents moved once — over the device time of the kernel's events in
one step.  The kernel's events are the step's Mosaic custom calls
(``shard_map.<n>[tpu_custom_call]``: flash attention, forward and
backward, is the only Pallas kernel in the training step)."""

from benchmarks import flops, trace_reduce
from benchmarks.runners.common import load_module

KERNEL = r"\[tpu_custom_call\]$"


def reduce(trace, counters, cell):
    seconds, events = trace_reduce.op_seconds(trace, KERNEL)
    steps = len(load_module("step_ms.train").step_events(trace))
    if cell.get("peaks") is None or not events or not steps:
        return None
    model = cell["config"]
    layers = model["num_hidden_layers"]
    rows, seq = counters["rows_per_step"], counters["seq_len"]
    ops = layers * flops.flash_flops(model, seq, rows, backward=True)
    nbytes = layers * flops.flash_bytes(
        model, seq, rows, backward=True, dtype=model["dtype"])
    # heads and rows are split evenly over the chips
    least_s, _ = flops.roofline_seconds(
        ops / cell["chips"], nbytes / cell["chips"], cell["peaks"],
        model["dtype"])
    return 100.0 * least_s / (seconds / steps)
