"""The whole training step's share of the chips' peak: tokens a second
times forward-and-backward operations a token (attention inside the
window included, nothing recomputed counted), over the chips' bf16
peak."""

from benchmarks import flops


def reduce(trace, counters, cell):
    if cell.get("peaks") is None or not counters.get("steps"):
        return None
    model = cell["config"]
    per_step = counters["rows_per_step"] * flops.train_flops(
        model, counters["seq_len"])
    peak = cell["peaks"]["flops_per_s"][model["dtype"]] * cell["chips"]
    return 100.0 * per_step * counters["steps"] / (
        counters["window_s"] * peak)
