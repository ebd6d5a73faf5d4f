"""The decode step against its roofline, for the gated window-and-full-
attention / softmax-routed-experts block with a shared expert: the
least time the chip could take for ONE step over the slots live when
the trace began — the weights outside the routed experts once (the
attention and gates of each layer's own heads, the dense FFN, routers,
shared experts, head), the matrices of the held experts that were HIT
(the chunk program's count, a step), the key and value of every
position a slot SEES on each layer, at the configuration's dtypes —
over the measured step (``decode_step_ms.serve``)."""

from benchmarks import flops_gated_swa_moe as fl
from benchmarks import moe_counts
from benchmarks.runners.common import load_reader


def reduce(trace, counters, cell):
    positions = counters.get("decode_positions")
    step_ms = load_reader("decode_step_ms.serve")(trace, counters, cell)
    counts = moe_counts.per_step(trace, counters)
    if (cell.get("peaks") is None or not positions or not step_ms
            or counts is None):
        return None
    model = cell["config"]
    ops, nbytes = fl.decode_step_work(
        model, positions, counts["moe_local_assignments"],
        counts["moe_experts_hit"], model["dtype"], model["cache_dtype"])
    least_s, _ = fl.roofline_seconds(
        ops, nbytes, cell["peaks"], model["dtype"])
    return 100.0 * least_s / (step_ms / 1e3)
