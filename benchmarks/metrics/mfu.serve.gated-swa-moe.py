"""The whole serving step's share of the chip's peak, for the gated
window-and-full-attention / softmax-routed-experts block with a shared
expert: forward operations (``flops_gated_swa_moe.forward_flops``) of
every prompt token prefilled and every token generated inside the
window — projections and gates at each layer's own query heads,
attention over the keys each query SEES on each layer, the dense FFN,
the router, the shared expert, the held share of the routed experts,
the head where a token is sampled — over the window's seconds times
the bf16 peak."""

from benchmarks import flops_gated_swa_moe as fl


def reduce(trace, counters, cell):
    if cell.get("peaks") is None or not counters.get("requests"):
        return None
    model = cell["config"]
    total = 0.0
    for r in counters["requests"]:
        p = r["prompt"]
        if r["in_window"]:
            total += fl.forward_flops(model, p)
        # generated token g is computed from position p + g - 1
        lo, hi = p + max(r["gen_open"], 1) - 1, p + r["gen_close"] - 1
        if hi > lo:
            total += fl.forward_flops(model, hi, start=lo, sampled=hi - lo)
    peak = cell["peaks"]["flops_per_s"][model["dtype"]] * cell["chips"]
    return 100.0 * total / (counters["window_s"] * peak)
