"""The decode step against its roofline: the least time the chip could
take for ONE step over the slots live when the trace began — weights
read once, each slot's keys and values inside the window read once,
at the configuration's dtypes — over the measured step
(``decode_step_ms.serve``)."""

from benchmarks import flops
from benchmarks.runners.common import load_reader


def reduce(trace, counters, cell):
    positions = counters.get("decode_positions")
    step_ms = load_reader("decode_step_ms.serve")(trace, counters, cell)
    if cell.get("peaks") is None or not positions or not step_ms:
        return None
    model = cell["config"]
    ops, nbytes = flops.decode_step_work(
        model, positions, model["dtype"], model["cache_dtype"])
    least_s, _ = flops.roofline_seconds(
        ops, nbytes, cell["peaks"], model["dtype"])
    return 100.0 * least_s / (step_ms / 1e3)
