"""The block-walking decode kernel (``ops/paged_attention.py``,
``block_decode_attention`` in the trace) against its roofline inside
the decode step of the gated block, rings (query groups of 72 / 8 = 9
on the sliding layers) and whole banks (48 / 8 = 6 on the full ones)
together: the least time the chip could take for one step's attention
over all layers — the key and value of every position a slot SEES
read once a layer, a score and a weighted sum a head and key at each
layer's own query heads (``flops_gated_swa_moe.bank_attention_work``),
over the slots live when the trace began — over the kernel's device
time a step (its events inside the decode chunk programs)."""

from benchmarks import flops_gated_swa_moe as fl
from benchmarks.runners.common import load_module

KERNEL = r"^block_decode_attention"


def reduce(trace, counters, cell):
    positions = counters.get("decode_positions")
    seconds = load_module(
        "grouped_matmul_roofline.serve").kernel_seconds_per_step(
            trace, counters, KERNEL)
    if cell.get("peaks") is None or not positions or not seconds:
        return None
    model = cell["config"]
    ops, nbytes = fl.bank_attention_work(
        model, positions, model["dtype"], model["cache_dtype"])
    least_s, _ = fl.roofline_seconds(
        ops, nbytes, cell["peaks"], model["dtype"])
    return 100.0 * least_s / seconds
