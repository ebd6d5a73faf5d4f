"""The whole serving step's share of the chip's peak: forward
operations of every prompt token prefilled and every token generated
inside the window (attention over the keys each really sees), over the
window's seconds times the bf16 peak."""

from benchmarks import flops


def reduce(trace, counters, cell):
    if cell.get("peaks") is None or not counters.get("requests"):
        return None
    model = cell["config"]
    total = 0.0
    for r in counters["requests"]:
        p = r["prompt"]
        if r["in_window"]:
            total += flops.forward_flops(model, p)
        # generated token g is computed from position p + g - 1
        lo, hi = p + max(r["gen_open"], 1) - 1, p + r["gen_close"] - 1
        if hi > lo:
            total += flops.forward_flops(model, hi, start=lo)
    peak = cell["peaks"]["flops_per_s"][model["dtype"]] * cell["chips"]
    return 100.0 * total / (counters["window_s"] * peak)
