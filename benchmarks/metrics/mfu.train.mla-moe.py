"""The whole training step's share of the chip's peak, for the
latent-attention / sigmoid-routed cells: the window's steps times the
forward-and-backward operations of a step (``flops_mla_moe_train``:
attention over the causal pairs at the query/key and value head sizes,
ONE routed expert per LOCAL assignment — the step's own counter, summed
over the window — nothing recomputed counted), over the window and the
chip's bf16 peak."""

from benchmarks import flops_mla_moe_train as fl

COUNT = "moe_local_assignments"


def reduce(trace, counters, cell):
    if (cell.get("peaks") is None or not counters.get("steps")
            or counters.get(COUNT) is None):
        return None
    model = cell["config"]
    steps = counters["steps"]
    ops = steps * fl.step_flops(
        model, counters["rows_per_step"], counters["seq_len"],
        counters[COUNT] / steps)
    peak = cell["peaks"]["flops_per_s"][model["dtype"]] * cell["chips"]
    return 100.0 * ops / (counters["window_s"] * peak)
