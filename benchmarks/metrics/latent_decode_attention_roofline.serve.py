"""The latent decode-attention kernel (``ops/latent_attention.py``,
``latent_decode_attention`` in the trace) against its roofline inside
the decode step: the least time the chip could take for one step's
attention over all layers — the latent row of every key a slot
SELECTS read once a layer, scores and sums in the absorbed form
(``flops_glm_dsa_moe.latent_attention_work``), over the slots live
when the trace began — over the kernel's device time a step (its
events inside the decode chunk programs).  The kernel reads every row
of a slot's live span under the selection's mask, not the selected
rows alone, so this share says how far the mask is from a gather."""

from benchmarks import flops_glm_dsa_moe as fl
from benchmarks.runners.common import load_module

KERNEL = r"^latent_decode_attention"


def reduce(trace, counters, cell):
    positions = counters.get("decode_positions")
    seconds = load_module(
        "grouped_matmul_roofline.serve").kernel_seconds_per_step(
            trace, counters, KERNEL)
    if cell.get("peaks") is None or not positions or not seconds:
        return None
    model = cell["config"]
    ops, nbytes = fl.latent_attention_work(
        model, positions, model["dtype"], model["cache_dtype"])
    least_s, _ = fl.roofline_seconds(
        ops, nbytes, cell["peaks"], model["dtype"])
    return 100.0 * least_s / seconds
