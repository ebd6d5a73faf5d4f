"""The whole serving step's share of the chip's peak, for the
latent-attention / sparse-index / routed-experts block: forward
operations (``flops_glm_dsa_moe.forward_flops``) of every prompt token
prefilled and every token generated inside the window — projections,
attention over the keys each query really selects, the index over
every visible key, the shared expert, one expert per LOCAL assignment,
the head where a token is sampled — over the window's seconds times
the bf16 peak.  The local share of the decode steps' assignments is
the one the chunk program counted in the traced window; prompt tokens
take the expectation (held / all experts)."""

from benchmarks import flops_glm_dsa_moe as fl
from benchmarks import moe_counts
from benchmarks.runners.common import load_module


def reduce(trace, counters, cell):
    if cell.get("peaks") is None or not counters.get("requests"):
        return None
    model = cell["config"]
    counts = moe_counts.per_step(trace, counters)
    share = None
    if counts and counts["moe_assignments"]:
        share = counts["moe_local_assignments"] / counts["moe_assignments"]
    total = 0.0
    for r in counters["requests"]:
        p = r["prompt"]
        if r["in_window"]:
            total += fl.forward_flops(model, p)
        # generated token g is computed from position p + g - 1
        lo, hi = p + max(r["gen_open"], 1) - 1, p + r["gen_close"] - 1
        if hi > lo:
            total += fl.forward_flops(
                model, hi, start=lo, local_share=share, sampled=hi - lo)
    peak = cell["peaks"]["flops_per_s"][model["dtype"]] * cell["chips"]
    return 100.0 * total / (counters["window_s"] * peak)
